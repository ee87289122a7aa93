"""FLOPs profiler.

Reference: ``profiling/flops_profiler/profiler.py:30`` — the reference
monkey-patches torch.nn.functional with counting wrappers. On TPU the
compiler already knows: ``jax.jit(fn).lower(...).compile().cost_analysis()``
returns XLA's own flop/byte counts for the exact compiled program,
including fusion effects — strictly more accurate than op-level patching.
"""

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

# THE cost-analysis helpers live in telemetry/explain.py (one place that
# handles dict-vs-list cost_analysis() shapes across jax versions and
# empty returns on CPU backends); re-exported here for API continuity.
from deepspeed_tpu.telemetry.explain import _cost, analyze_fn  # noqa: F401
from deepspeed_tpu.utils.logging import log_dist


class FlopsProfiler:
    """Step-granular profiler attached to an engine (reference
    profiler.py API: start_profile/stop_profile/print_model_profile)."""

    def __init__(self, engine=None, config=None):
        self.engine = engine
        self.config = config
        self._t0: Optional[float] = None
        self._steps = 0
        self.flops_per_step: Optional[float] = None
        self.last_tflops: Optional[float] = None

    def start_profile(self) -> None:
        self._t0 = time.perf_counter()
        self._steps = 0

    def step(self) -> None:
        self._steps += 1

    def stop_profile(self) -> Dict[str, float]:
        dt = time.perf_counter() - (self._t0 or time.perf_counter())
        result = {"seconds": dt, "steps": self._steps}
        if self.engine is not None and self.engine.model.flops_per_token:
            tokens = self._steps * int(self.engine.config.train_batch_size) \
                * (self.engine.model.tokens_per_sample or 1)
            flops = self.engine.model.flops_per_token * tokens
            result["tflops"] = flops / max(dt, 1e-9) / 1e12
            self.last_tflops = result["tflops"]
            # interval MFU through the shared peak-FLOPs table; unlike the
            # engine's per-step host-time gauge this window is explicitly
            # opened/closed by the caller, so it can bracket a synced region
            from deepspeed_tpu.telemetry import registry
            from deepspeed_tpu.telemetry.sampler import mfu
            result["mfu"] = mfu(flops, dt, n_devices=jax.device_count())
            registry.gauge(
                "train/mfu_profiled",
                help="MFU over the last start/stop_profile window").set(
                result["mfu"])
        return result

    def print_profile(self) -> None:
        log_dist(f"flops profiler: {self.stop_profile()}")


def _abstract(tree):
    """Pytree of arrays/shapes → ShapeDtypeStructs (lower() takes them
    directly, so nothing is ever allocated — 70B profiles are free)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def module_profile(dec_cfg, batch_size: int = 1,
                   seq_len: Optional[int] = None,
                   dtype=None, top_k: int = 10,
                   measure: bool = False,
                   measure_iters: int = 8) -> Dict[str, Any]:
    """Per-module forward flops/bytes/params breakdown (reference
    flops_profiler builds this tree by monkey-patching every torch module,
    profiler.py:511-861; here each named component is lowered separately
    over ABSTRACT shapes and XLA's own cost analysis is read back —
    fusion-accurate per component, nothing allocated or executed).

    Returns a tree ``{name, flops, bytes, params, pct, children: [...]}``
    plus ``top`` — the top-k leaf cost centers with percentages. The
    per-layer row is measured once and multiplied by num_layers (layers
    are homogeneous by construction — one stacked scan block).

    ``measure=True`` additionally RUNS each component jitted on the
    current backend with random concrete inputs and attaches measured
    wall time (``ms`` per row, iteration-chained inside one jit with a
    scalar fetch so per-call dispatch noise does not pollute the
    number — the reference profiler's measured per-module duration,
    profiler.py:511). Costs one compile + ``measure_iters`` runs per
    component.
    """
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as T

    cfg = dec_cfg
    t = seq_len or cfg.max_seq_len
    b = batch_size
    dt = dtype or jnp.float32
    abstract_params = jax.eval_shape(
        lambda r: T.init_params(cfg, r, dtype=dt), jax.random.PRNGKey(0))
    layer0 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
        abstract_params["layers"])
    tokens = jax.ShapeDtypeStruct((b, t), np.int32)
    x = jax.ShapeDtypeStruct((b, t, cfg.hidden_size), dt)
    positions = jax.ShapeDtypeStruct((b, t), np.int32)

    def n_params(tree):
        return int(sum(int(np.prod(s.shape))
                       for s in jax.tree.leaves(tree)))

    def sincos(pos):
        if cfg.pos_emb == "rope":
            return T.rope_table(cfg, pos)
        return (jnp.zeros((b, t, 0), jnp.float32),) * 2

    def embed_fn(em, tok):
        return T.embed_tokens(cfg, em, tok,
                              jnp.broadcast_to(jnp.arange(t)[None], (b, t)))

    def attn_fn_(p, xx, pos):
        sin, cos = sincos(pos)
        return T._attention_block(cfg, p, xx, sin, cos,
                                  T.default_attention(cfg))

    def mlp_fn(p, xx):
        if cfg.num_experts:
            from functools import partial
            from deepspeed_tpu.parallel.moe import moe_layer
            fn = partial(moe_layer, top_k=cfg.num_experts_per_tok,
                         ep_axis=None)
            return fn(cfg, p, xx)
        return T._mlp(cfg, p, xx)

    def norm_fn(p, xx):
        return T._norm(cfg, p, xx)

    def head_fn(params, xx):
        xn = T._norm(cfg, params["final_norm"], xx)
        return T.lm_logits(cfg, params, xn)

    mlp_key = "moe" if cfg.num_experts else "mlp"
    rows = [
        ("embed", embed_fn, (abstract_params["embed"], tokens),
         n_params(abstract_params["embed"])),
        ("layer.attention", attn_fn_, (layer0["attn"], x, positions),
         n_params(layer0["attn"])),
        (f"layer.{mlp_key}", mlp_fn, (layer0[mlp_key], x),
         n_params(layer0[mlp_key])),
        ("layer.norms", norm_fn, (layer0["ln1"], x),
         n_params({k: v for k, v in layer0.items()
                   if k.startswith("ln")})),
        ("head(norm+logits)", head_fn,
         ({"final_norm": abstract_params["final_norm"],
           "embed": abstract_params["embed"],
           **({"lm_head": abstract_params["lm_head"]}
              if "lm_head" in abstract_params else {})}, x),
         0 if cfg.tie_embeddings else
         n_params(abstract_params.get("lm_head", {}))),
    ]

    def _measure_ms(fn, abstract_args) -> float:
        """Wall ms per call: concrete random inputs, one jit whose body
        chains `measure_iters` dependent calls, scalar fetched."""
        import time as _time
        from jax import lax as _lax

        def _concrete(s):
            if np.issubdtype(s.dtype, np.integer):
                return jnp.zeros(s.shape, s.dtype)
            return jnp.full(s.shape, 0.01, s.dtype)

        args_c = jax.tree.map(_concrete, tuple(abstract_args))

        def chained(*a):
            def step(_, carry):
                # thread the carry into the inputs as a runtime ~0 so
                # XLA cannot hoist the body out of the loop
                eps = carry * 1e-30

                def bump(l):
                    if jnp.issubdtype(l.dtype, jnp.floating):
                        return l + eps.astype(l.dtype)
                    return l
                out = fn(jax.tree.map(bump, a[0]), *a[1:])
                out0 = out[0] if isinstance(out, tuple) else out
                return jnp.sum(out0.astype(jnp.float32)) * 1e-9

            return _lax.fori_loop(0, measure_iters, step, jnp.float32(0.0))
        jf = jax.jit(chained)
        float(jf(*args_c))                       # compile + warm
        t0 = _time.perf_counter()
        float(jf(*args_c))
        return (_time.perf_counter() - t0) / measure_iters * 1e3

    leaves = []
    for name, fn, args, params in rows:
        c = _cost(fn, *args)
        mult = cfg.num_layers if name.startswith("layer.") else 1
        row = {"name": name + (f" x{mult}" if mult > 1 else ""),
               "flops": c["flops"] * mult,
               "bytes": c["bytes"] * mult,
               "params": params * mult}
        if measure:
            row["ms"] = _measure_ms(fn, args) * mult
        leaves.append(row)
    total_fl = sum(r["flops"] for r in leaves) or 1.0
    for r in leaves:
        r["pct"] = 100.0 * r["flops"] / total_fl
    tree = {"name": f"model(b={b}, t={t})",
            "flops": sum(r["flops"] for r in leaves),
            "bytes": sum(r["bytes"] for r in leaves),
            "params": sum(r["params"] for r in leaves),
            "children": leaves,
            "top": sorted(leaves,
                          key=lambda r: -r.get("ms", r["flops"]))[:top_k]}
    if measure:
        tree["ms"] = sum(r["ms"] for r in leaves)
    return tree


def format_module_profile(tree: Dict[str, Any]) -> str:
    """Human-readable table (reference print_model_profile analogue)."""
    lines = [f"{tree['name']}: {tree['flops'] / 1e9:.2f} GFLOPs fwd, "
             f"{tree['bytes'] / 2**30:.2f} GiB moved, "
             f"{tree['params'] / 1e6:.1f}M params"]
    for r in sorted(tree["children"], key=lambda r: -r["flops"]):
        lines.append(
            f"  {r['name']:<24s} {r['flops'] / 1e9:10.2f} GF "
            f"{r['pct']:5.1f}%  {r['bytes'] / 2**20:10.1f} MiB  "
            f"{r['params'] / 1e6:8.2f}M"
            + (f"  {r['ms']:8.2f} ms" if "ms" in r else ""))
    return "\n".join(lines)


def get_model_profile(fn: Callable, args: Tuple,
                      print_profile: bool = True) -> Tuple[float, float, int]:
    """Reference get_model_profile API: returns (flops, macs, params).

    'macs' ≈ flops/2 (XLA counts multiply-adds as 2 flops); params counted
    from the first arg when it is a pytree of arrays.
    """
    cost = analyze_fn(fn, *args)
    flops = cost["flops"]
    params = 0
    if args:
        try:
            params = sum(int(np.prod(x.shape))
                         for x in jax.tree.leaves(args[0]))
        except Exception:
            params = 0
    if print_profile:
        log_dist(f"model profile: flops={flops:.3e} macs={flops / 2:.3e} "
                 f"params={params / 1e6:.1f}M "
                 f"bytes={cost.get('bytes_accessed', 0):.3e}")
    return flops, flops / 2, params
