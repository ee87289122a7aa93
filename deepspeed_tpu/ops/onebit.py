"""1-bit Adam / 1-bit LAMB / 0/1 Adam — communication-compressed optimizers.

Reference: ``runtime/fp16/onebit/adam.py:14`` (OnebitAdam), ``lamb.py:16``
(OnebitLamb), ``zoadam.py`` (0/1 Adam), over the compressed backends
(runtime/comm/nccl.py:52). The algorithm: a **warmup** phase
(``freeze_step`` steps) runs exact Adam with full-precision gradient
averaging while the variance estimate stabilizes; after the freeze the
variance is FROZEN and each worker updates its momentum with its LOCAL
gradient, then exchanges only the SIGN bits of the momentum through the
error-feedback 1-bit allreduce (comm/compressed.py) — 32× less traffic
per step, the blogs' up-to-26× comm reduction.

1-bit LAMB adds layerwise adaptation: during warmup the exact LAMB trust
ratio ||w||/||update|| is applied per parameter leaf and its EMA
recorded; in the compressed phase the update is scaled by the FROZEN
per-leaf coefficient (reference lamb.py freezes ``scaling_coeff`` the
same way — fresh trust ratios can't be computed without exact global
statistics).

TPU design: one explicit ``shard_map`` step over 'data' (quantized/
compressed collectives can't be expressed as GSPMD annotations — same
stance as runtime/zero/zeropp.py). Params and m/v stay REPLICATED (the
reference requires ZeRO stage 0 with 1-bit optimizers too); the error-
feedback buffers are per-device state carried as [world, ...] arrays
sharded over 'data'. Restrictions (validated): zero stage 0, bf16/fp32,
no offload/pipeline, no gradient clipping in the compressed phase (the
exact global norm is never materialized — reference has the same
limitation).
"""

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.compressed import (compressed_allreduce,
                                           init_error_buffers, padded_size)
from deepspeed_tpu.runtime.zero.offload import FlatLayout
from deepspeed_tpu.utils.logging import log_dist

ONEBIT_NAMES = ("onebitadam", "onebit_adam", "zerooneadam",
                "onebitlamb", "onebit_lamb")


def validate_onebit(engine) -> None:
    cfg = engine.config
    if cfg.zero_optimization.stage != 0:
        raise ValueError("1-bit Adam requires ZeRO stage 0 (reference "
                         "onebit/adam.py restriction: momentum comm "
                         "replaces the grad allreduce)")
    for ax in ("model", "seq", "pipe", "expert", "data_inner"):
        if engine.mesh.shape[ax] != 1:
            raise ValueError(f"1-bit Adam runs over the 'data' axis only; "
                             f"mesh axis '{ax}' = {engine.mesh.shape[ax]}")
    if engine.fp16_enabled:
        raise ValueError("1-bit Adam here requires bf16/fp32 (fp16 "
                         "overflow handling needs exact grads)")
    if engine.offload_enabled:
        raise ValueError("1-bit Adam and offload_optimizer are exclusive")
    if engine.model.pipeline_loss_fn is not None:
        raise ValueError("1-bit Adam does not compose with pipeline")


def _is_zeroone(opt_type: str) -> bool:
    return "zeroone" in opt_type.lower().replace("-", "").replace("_", "")


def init_onebit_state(engine) -> None:
    """Replicated flat master/m/v + per-device error-feedback buffers."""
    mesh = engine.mesh
    world = mesh.shape["data"]
    layout = FlatLayout(engine._abstract_params)
    engine._onebit_layout = layout
    total = layout.total
    padded = padded_size(total, world)
    engine._onebit_padded = padded
    zeroone = _is_zeroone(engine.config.optimizer.type)

    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("data"))
    flat_params = jax.jit(
        lambda p: layout.flatten_device(p, jnp.float32),
        out_shardings=rep)(engine.params)
    engine.opt_state = {
        "master": flat_params,
        "m": jax.device_put(jnp.zeros((total,), jnp.float32), rep),
        "v": jax.device_put(jnp.zeros((total,), jnp.float32), rep),
        "werr": jax.device_put(jnp.zeros((world, padded), jnp.float32),
                               dp),
        "serr": jax.device_put(
            jnp.zeros((world, padded // world), jnp.float32), dp),
        "step": jax.device_put(jnp.zeros((), jnp.int32), rep),
        # per-leaf LAMB trust-ratio EMA (frozen after warmup); carried
        # by the adam variants too so the state treedef is uniform
        "coeff": jax.device_put(
            jnp.ones((len(layout.sizes),), jnp.float32), rep),
        # 0/1 Adam extras (zoadam.py state): the momentum accumulator u
        # (local updates applied between syncs), accumulated lr, and the
        # adaptive variance/local-step interval policy scalars. The u
        # buffer is param-sized so only 0/1 Adam allocates it.
        "u": jax.device_put(
            jnp.zeros((total if zeroone else 0,), jnp.float32), rep),
        "lrs": jax.device_put(jnp.zeros((), jnp.float32), rep),
        "var_interval": jax.device_put(jnp.ones((), jnp.int32), rep),
        "var_counter": jax.device_put(jnp.zeros((), jnp.int32), rep),
        "local_interval": jax.device_put(jnp.ones((), jnp.int32), rep),
        "local_counter": jax.device_put(jnp.zeros((), jnp.int32), rep),
        # telemetry: how many exact (fp32 pmean) vs 1-bit collectives the
        # schedule actually issued — the comm-savings invariant under test
        "exact_comms": jax.device_put(jnp.zeros((), jnp.int32), rep),
        "onebit_comms": jax.device_put(jnp.zeros((), jnp.int32), rep),
    }
    engine._state_shardings = jax.tree.map(
        lambda x: x.sharding, engine.opt_state)
    log_dist(f"{'0/1' if zeroone else '1-bit'} Adam: "
             f"{total / 1e6:.1f}M params, dp={world}, "
             f"compressed collectives per the interval policy")


def build_onebit_step(engine) -> None:
    cfg = engine.config
    mesh = engine.mesh
    world = mesh.shape["data"]
    layout = engine._onebit_layout
    total = layout.total
    padded = engine._onebit_padded
    compute_dtype = engine.compute_dtype
    gas = int(cfg.gradient_accumulation_steps)
    lr_schedule = engine.lr_schedule
    loss_fn = engine.model.loss_fn

    p = dict(cfg.optimizer.params or {})
    betas = p.get("betas", (0.9, 0.999))
    b1, b2 = float(betas[0]), float(betas[1])
    eps = float(p.get("eps", 1e-8))
    wd = float(p.get("weight_decay", 0.0))
    freeze_step = int(p.get("freeze_step", 100))
    is_lamb = "lamb" in cfg.optimizer.type.lower()
    is_zeroone = _is_zeroone(cfg.optimizer.type)
    # 0/1 Adam policy knobs (reference zoadam.py defaults)
    var_freeze_step = int(p.get("var_freeze_step", 100000))
    var_update_scaler = int(p.get("var_update_scaler", 16))
    local_step_scaler = int(p.get("local_step_scaler", 32768))
    local_step_clipper = int(p.get("local_step_clipper", 16))
    # LAMB trust-ratio clip + EMA factor (reference lamb.py max_coeff /
    # min_coeff / coeff_beta)
    coeff_max = float(p.get("max_coeff", 10.0))
    coeff_min = float(p.get("min_coeff", 0.01))
    coeff_beta = float(p.get("coeff_beta", 0.9))
    n_seg = len(layout.sizes)
    seg_ids = jnp.asarray(np.repeat(np.arange(n_seg), layout.sizes),
                          jnp.int32)

    def seg_trust(master, upd):
        """Per-leaf LAMB trust ratio ||w||/||upd||, clipped; zero-norm
        leaves (zero-initialized biases at step 1) get the reference's
        neutral 1.0 (lamb.py: lamb_coeff=1 when either norm is 0) — the
        clip floor would otherwise freeze them 100x down."""
        wn = jnp.sqrt(jax.ops.segment_sum(master * master, seg_ids,
                                          num_segments=n_seg))
        un = jnp.sqrt(jax.ops.segment_sum(upd * upd, seg_ids,
                                          num_segments=n_seg))
        trust = jnp.clip(wn / jnp.maximum(un, 1e-12), coeff_min, coeff_max)
        return jnp.where((wn == 0) | (un == 0), 1.0, trust)

    def body(params, opt, batch, step, rng):
        def micro(carry, mb):
            acc, r = carry
            r, sub = jax.random.split(r)

            def lf(pp):
                out = loss_fn(pp, mb, sub)
                return out[0] if isinstance(out, tuple) else out

            loss, grads = jax.value_and_grad(lf)(params)
            return (acc + layout.flatten_device(grads, jnp.float32), r), \
                loss

        acc0 = jnp.zeros((total,), jnp.float32)
        (g_local, _), losses = lax.scan(micro, (acc0, rng), batch)
        g_local = g_local * (1.0 / gas)

        master, m, v = opt["master"], opt["m"], opt["v"]
        werr, serr = opt["werr"][0], opt["serr"][0]
        t_new = opt["step"] + 1

        def warmup(_):
            g = lax.pmean(g_local, "data")
            m1 = b1 * m + (1 - b1) * g
            v1 = b2 * v + (1 - b2) * g * g
            return m1, v1, werr, serr

        def compressed(_):
            # local momentum then 1-bit error-feedback allreduce of it
            ml = b1 * m + (1 - b1) * g_local
            ml_pad = jnp.concatenate(
                [ml, jnp.zeros((padded - total,), jnp.float32)])
            m_avg, w2, s2 = compressed_allreduce(ml_pad, werr, serr,
                                                 "data")
            return m_avg[:total], v, w2, s2       # variance FROZEN

        m1, v1, w2, s2 = lax.cond(t_new <= freeze_step, warmup,
                                  compressed, None)
        bc1 = 1 - b1 ** t_new.astype(jnp.float32)
        bc2 = 1 - b2 ** jnp.minimum(
            t_new, freeze_step).astype(jnp.float32)
        lr = lr_schedule(step)
        upd = (m1 / bc1) / (jnp.sqrt(v1 / bc2) + eps)
        if wd:
            upd = upd + wd * master
        coeff = opt["coeff"]
        if is_lamb:
            # warmup: exact per-leaf trust ratio, EMA recorded; after the
            # freeze the EMA is FROZEN and reused (reference lamb.py
            # scaling_coeff freeze)
            in_warmup = t_new <= freeze_step
            trust_now = seg_trust(master, upd)
            trust = jnp.where(in_warmup, trust_now, coeff)
            coeff = jnp.where(
                in_warmup,
                coeff_beta * coeff + (1 - coeff_beta) * trust_now, coeff)
            upd = upd * trust[seg_ids]
        master1 = master - lr * upd
        new_flat = master1.astype(compute_dtype)
        loss = lax.pmean(jnp.mean(losses), "data")
        mnorm = jnp.sqrt(jnp.sum(jnp.square(m1)))
        new_opt = dict(opt, master=master1, m=m1, v=v1,
                       werr=w2[None], serr=s2[None], step=t_new,
                       coeff=coeff)
        return new_flat, new_opt, loss, mnorm, lr

    def body_zeroone(params, opt, batch, step, rng):
        """0/1 Adam (reference zoadam.py:14, arXiv:2202.06009).

        Phase 1 (step <= var_freeze_step) — adaptive variance updates:
        on steps divisible by ``var_interval`` the gradient is averaged
        EXACTLY and both moments update; on all other steps only the
        momentum updates, from the 1-bit error-feedback-compressed
        gradient. ``var_interval`` doubles every ``var_update_scaler``
        variance updates, so exact collectives become exponentially rare.

        Phase 2 (after the freeze) — local steps: momentum updates from
        the LOCAL gradient and the worker takes the step with NO
        communication, accumulating applied updates in ``u``; every
        ``local_interval`` steps the local drift is undone, the
        accumulated momentum is 1-bit-allreduced, and params/momentum are
        reset from the global average (zoadam.py:246-266).
        ``local_interval`` doubles every ``local_step_scaler`` steps,
        clipped at ``local_step_clipper``."""
        def micro(carry, mb):
            acc, r = carry
            r, sub = jax.random.split(r)

            def lf(pp):
                out = loss_fn(pp, mb, sub)
                return out[0] if isinstance(out, tuple) else out

            loss, grads = jax.value_and_grad(lf)(params)
            return (acc + layout.flatten_device(grads, jnp.float32), r), \
                loss

        acc0 = jnp.zeros((total,), jnp.float32)
        (g_local, _), losses = lax.scan(micro, (acc0, rng), batch)
        g_local = g_local * (1.0 / gas)

        master, m, v, u = opt["master"], opt["m"], opt["v"], opt["u"]
        t_new = opt["step"] + 1
        lr = lr_schedule(step)
        # phase-boundary error-buffer reset (zoadam.py
        # reinitial_error_buffer: the errors switch metric from gradient
        # to accumulated momentum)
        at_boundary = t_new == (var_freeze_step + 1)
        werr = jnp.where(at_boundary, 0.0, opt["werr"][0])
        serr = jnp.where(at_boundary, 0.0, opt["serr"][0])
        pad_z = jnp.zeros((padded - total,), jnp.float32)

        def phase1(_):
            var_step = (t_new % opt["var_interval"]) == 0

            def exact(_):
                g = lax.pmean(g_local, "data")
                m1 = b1 * m + (1 - b1) * g
                v1 = b2 * v + (1 - b2) * g * g
                return (m1, v1, werr, serr,
                        opt["exact_comms"] + 1, opt["onebit_comms"])

            def onebit(_):
                g_avg, w2, s2 = compressed_allreduce(
                    jnp.concatenate([g_local, pad_z]), werr, serr, "data")
                m1 = b1 * m + (1 - b1) * g_avg[:total]
                return (m1, v, w2, s2,
                        opt["exact_comms"], opt["onebit_comms"] + 1)

            m1, v1, w2, s2, ec, oc = lax.cond(var_step, exact, onebit,
                                              None)
            upd = m1 / (jnp.sqrt(v1) + eps)
            if wd:
                upd = upd + wd * master
            master1 = master - lr * upd
            vc = jnp.where(var_step, opt["var_counter"] + 1,
                           opt["var_counter"])
            dbl = vc >= var_update_scaler
            vi = jnp.where(dbl, opt["var_interval"] * 2,
                           opt["var_interval"])
            vc = jnp.where(dbl, 0, vc)
            return (master1, m1, v1, u, opt["lrs"], w2, s2, vi, vc,
                    opt["local_interval"], opt["local_counter"], ec, oc)

        def phase2(_):
            # local momentum + local step, zero communication
            m1 = b1 * m + (1 - b1) * g_local
            denom = jnp.sqrt(v) + eps
            upd = m1 / denom
            if wd:
                upd = upd + wd * master
            master1 = master - lr * upd
            u1 = u - lr * upd
            lrs1 = opt["lrs"] + lr
            sync = (t_new % opt["local_interval"]) == 0

            def do_sync(_):
                # undo local drift, average the accumulated momentum
                # (u scaled back to momentum units), re-apply globally
                undone = master1 - u1
                buf = u1 * denom
                buf_avg, w2, s2 = compressed_allreduce(
                    jnp.concatenate([buf, pad_z]), werr, serr, "data")
                buf_avg = buf_avg[:total]
                m2 = -buf_avg / jnp.maximum(lrs1, 1e-20)
                p2 = undone + buf_avg / denom
                return (p2, m2, jnp.zeros_like(u1),
                        jnp.zeros_like(lrs1), w2, s2,
                        opt["onebit_comms"] + 1)

            def no_sync(_):
                return (master1, m1, u1, lrs1, werr, serr,
                        opt["onebit_comms"])

            p2, m2, u2, lrs2, w2, s2, oc = lax.cond(sync, do_sync,
                                                    no_sync, None)
            lc = opt["local_counter"] + 1
            dbl = lc >= local_step_scaler
            li = jnp.where(
                dbl, jnp.minimum(local_step_clipper,
                                 opt["local_interval"] * 2),
                opt["local_interval"])
            lc = jnp.where(dbl, 0, lc)
            return (p2, m2, v, u2, lrs2, w2, s2, opt["var_interval"],
                    opt["var_counter"], li, lc, opt["exact_comms"], oc)

        (master1, m1, v1, u1, lrs1, w2, s2, vi, vc, li, lc, ec, oc) = \
            lax.cond(t_new > var_freeze_step, phase2, phase1, None)
        new_flat = master1.astype(compute_dtype)
        loss = lax.pmean(jnp.mean(losses), "data")
        mnorm = jnp.sqrt(jnp.sum(jnp.square(m1)))
        new_opt = dict(opt, master=master1, m=m1, v=v1, u=u1, lrs=lrs1,
                       werr=w2[None], serr=s2[None], step=t_new,
                       var_interval=vi, var_counter=vc,
                       local_interval=li, local_counter=lc,
                       exact_comms=ec, onebit_comms=oc)
        return new_flat, new_opt, loss, mnorm, lr

    param_specs = jax.tree.map(lambda _: P(), engine.params)
    opt_specs = {"master": P(), "m": P(), "v": P(),
                 "werr": P("data"), "serr": P("data"), "step": P(),
                 "coeff": P(), "u": P(), "lrs": P(),
                 "var_interval": P(), "var_counter": P(),
                 "local_interval": P(), "local_counter": P(),
                 "exact_comms": P(), "onebit_comms": P()}
    step_body = body_zeroone if is_zeroone else body

    def fused_step(params, opt_state, scaler, batch, step, rng):
        batch_specs = jax.tree.map(
            lambda x: P(None, "data", *([None] * (np.ndim(x) - 2))),
            batch)
        new_flat, new_opt, loss, mnorm, lr = shard_map(
            step_body, mesh=mesh,
            in_specs=(param_specs, opt_specs, batch_specs, P(), P()),
            out_specs=(P(), opt_specs, P(), P(), P()),
            check_vma=False,
        )(params, opt_state, batch, step, rng)
        new_params = layout.unflatten_device(
            new_flat, [compute_dtype if jnp.issubdtype(d, jnp.floating)
                       else d for d in layout.dtypes])
        new_params = lax.with_sharding_constraint(
            new_params, engine._param_shardings)
        metrics = {"loss": loss, "lr": lr, "grad_norm": mnorm,
                   "loss_scale": scaler.scale,
                   "overflow": jnp.zeros((), jnp.int32)}
        return new_params, new_opt, scaler, metrics

    # the module's name in a device trace: the fused step's kind
    fused_step.__name__ = fused_step.__qualname__ = "fused_step_onebit"
    engine._fused_step = jax.jit(fused_step, donate_argnums=(0, 1))
    engine._grad_step = None
    engine._acc_add = None
    engine._update_step = None
    engine._rng = jax.random.PRNGKey(cfg.seed + 1)
