"""Mixture-of-Experts with expert parallelism — TPU-native.

Reference: ``deepspeed/moe/sharded_moe.py`` (``top1gating``:183,
``top2gating``:290, ``topkgating``:374, ``MOELayer``:536 with its einsum
dispatch masks, ``_AllToAll``:96) and ``deepspeed/moe/layer.py:17``.

The reference dispatches tokens to experts with an explicit
``dist.all_to_all_single`` over the EP process group. Here the dispatch is
the GShard einsum formulation — build ``[S,E,C]`` dispatch/combine masks,
``einsum('sec,sd->ecd')`` into per-expert buffers — and the expert dim of
the buffer carries a sharding constraint over the ``'expert'`` mesh axis,
so XLA lowers the regroup to the same ICI all-to-all, overlapped with the
expert GEMMs. Capacity is static (jit-friendly); tokens over capacity are
dropped (``drop_tokens``) or routed best-effort via the mask arithmetic.

Load-balance auxiliary loss per reference top1gating: ``E · Σ_e mē·c̄e``.
RTS (random token selection, reference :225): with ``use_rts`` the
capacity-slot priority is a random token permutation per step (keyed
from the engine's per-step rng), matching the reference's default
top-1 behavior; off → deterministic sequence-order priority.
"""

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.comms_logger import comms_logger


def topk_gates_t(gates_t: jax.Array, k: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Transposed top-k: ``gates_t`` [E, S] → (topv_t, topi_t) [k, S].

    The whole dropless routing chain runs in this [E, S] orientation —
    E on SUBLANES, tokens on lanes — so softmax/max/argmax reduce over
    8 sublanes with all 128 lanes busy. The [S, E] orientation puts E
    on lanes (8 of 128 used) and measured ~2 ms/layer of pure layout
    waste at [16K, 8] fwd+bwd on v5e (the same finding that shaped
    ``aligned_dispatch``'s [E, R0] histogram).
    """
    e = gates_t.shape[0]
    rows = jnp.arange(e, dtype=jnp.int32)
    g = gates_t
    vals, idxs = [], []
    for _ in range(k):
        v = jnp.max(g, axis=0)
        i = jnp.argmax(g, axis=0).astype(jnp.int32)
        vals.append(v)
        idxs.append(i)
        g = jnp.where(rows[:, None] == i[None, :], -jnp.inf, g)
    return jnp.stack(vals, 0), jnp.stack(idxs, 0)


def _capacity(num_tokens: int, num_experts: int, k: int,
              capacity_factor: float, min_capacity: int) -> int:
    """Reference sharded_moe.py:_capacity — static on TPU (shapes fixed
    at trace time)."""
    cap = math.ceil(num_tokens * k / num_experts * capacity_factor)
    return max(cap, min_capacity)


def topk_gating(logits: jax.Array, k: int, capacity: int,
                norm_probs: bool = True,
                rts_key: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k gating with capacity (reference topkgating:374).

    logits: [S, E] fp32 → (dispatch [S,E,C] bool, combine [S,E,C] f32,
    aux_loss scalar). Tokens whose per-expert slot position exceeds
    ``capacity`` are dropped; callers wanting the reference's
    ``drop_tokens=False`` semantics pass ``capacity == S`` (static worst
    case — the TPU answer to the reference's dynamic capacity raise).
    ``norm_probs``: renormalize the selected gate values (Mixtral); off
    for Qwen2-MoE's norm_topk_prob=False raw-softmax convention.
    ``rts_key``: Random Token Selection (reference top1gating:225) —
    capacity slots are claimed in a RANDOM token order instead of
    sequence order, so over-capacity drops don't always punish the same
    trailing tokens. None = deterministic sequence-order priority.
    """
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)                   # [S,E]
    topv, topi = lax.top_k(gates, k)                          # [S,k]
    if norm_probs:   # reference topkgating norm
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # aux loss from the top-1 assignment (reference top1gating:262)
    mask1 = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    aux = jnp.sum(me * ce) * e

    perm = None
    if rts_key is not None:
        perm = jax.random.permutation(rts_key, s)

    # positions: running per-expert counts across the k choices
    counts = jnp.zeros((e,), jnp.int32)
    dispatch = jnp.zeros((s, e, capacity), jnp.bool_)
    combine = jnp.zeros((s, e, capacity), jnp.float32)
    for i in range(k):
        mask_i = jax.nn.one_hot(topi[:, i], e, dtype=jnp.int32)   # [S,E]
        if perm is not None:
            # claim slots in permuted (random-priority) order, then
            # scatter the positions back to token order
            pos_p = jnp.cumsum(mask_i[perm], axis=0) - mask_i[perm] \
                + counts[None, :]
            pos_i = jnp.zeros_like(pos_p).at[perm].set(pos_p)
        else:
            pos_i = jnp.cumsum(mask_i, axis=0) - mask_i + counts[None, :]
        pos_tok = jnp.sum(pos_i * mask_i, axis=1)                 # [S]
        keep = pos_tok < capacity
        oh_cap = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
        sel = (mask_i.astype(jnp.float32) * keep[:, None])        # [S,E]
        d_i = sel[:, :, None] * oh_cap[:, None, :]                # [S,E,C]
        dispatch = jnp.logical_or(dispatch, d_i > 0)
        combine = combine + d_i * topv[:, i][:, None, None]
        counts = counts + jnp.sum(mask_i * keep[:, None].astype(jnp.int32),
                                  axis=0)
    return dispatch, combine, aux


def _shared_expert(sh, xf: jax.Array) -> jax.Array:
    """Qwen2-MoE/DeepSeek dense shared expert on every token.

    xf [S,d] → [S,d]; handles int8/fp8 weight_quant leaves (scale-suffix
    convention, ops/quantized_linear.py) and the optional sigmoid gate.
    ONE implementation shared by the capacity and dropless paths. A tree
    with no ``wg`` is an UN-GATED ``relu²`` unit, ``relu(x·wi)²·wo``
    (Nemotron-H; a typed stack's, never quantized)."""
    from deepspeed_tpu.ops.quantized_linear import SCALE_SUFFIX
    if "wg" not in sh:
        return jnp.einsum("sh,hd->sd", _relu2(jnp.einsum(
            "sd,dh->sh", xf, sh["wi"])), sh["wo"])
    if "wg" + SCALE_SUFFIX in sh:
        # qmatmul_tp so int8/fp8 shared-expert weights TP-shard like the
        # dense MLP (col gate/up, row down); only reached from the
        # capacity path — dropless is unquantized by construction, so
        # no nested-manual-mesh conflict with its batch shard_map
        from deepspeed_tpu.ops.quantized_linear import qmatmul_tp
        gate_s = qmatmul_tp(xf, sh["wg"], sh["wg_scale"], role="col",
                            out_dtype=xf.dtype)
        up_s = qmatmul_tp(xf, sh["wi"], sh["wi_scale"], role="col",
                          out_dtype=xf.dtype)
        s_out = qmatmul_tp(jax.nn.silu(gate_s) * up_s, sh["wo"],
                           sh["wo_scale"], role="row",
                           out_dtype=xf.dtype)
    else:
        gate_s = jnp.einsum("sd,dh->sh", xf, sh["wg"])
        up_s = jnp.einsum("sd,dh->sh", xf, sh["wi"])
        s_out = jnp.einsum("sh,hd->sd", jax.nn.silu(gate_s) * up_s,
                           sh["wo"])
    if "gate" in sh:
        s_out = s_out * jax.nn.sigmoid(
            jnp.einsum("sd,do->so", xf.astype(jnp.float32),
                       sh["gate"].astype(jnp.float32))).astype(xf.dtype)
    return s_out


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _use_pallas_gmm(d: int, f: int) -> bool:
    """Kernel selection for the dropless FFN: DSTPU_MOE_KERNEL ∈
    auto (default: Pallas on TPU when shapes tile) | pallas | xla."""
    import os
    from deepspeed_tpu.ops import grouped_matmul as gmm
    mode = os.environ.get("DSTPU_MOE_KERNEL", "auto")
    if mode == "xla":
        return False
    if mode == "pallas":
        return True
    return jax.default_backend() == "tpu" and gmm.supported(d, f)


def _dropless_ffn(p, xf: jax.Array, topv: jax.Array, topi: jax.Array,
                  top_k: int) -> jax.Array:
    """Token-local dropless dispatch: sort + grouped matmul + combine.

    xf [S,d], topv/topi [k,S] SLOT-MAJOR (``topk_gates_t``'s layout —
    tokens on lanes) → out [S,d]. Every op is per-token local
    (no collectives), so this body runs unchanged either globally or as
    the per-shard body of a shard_map over the batch axes.

    Two grouped-matmul backends (ops/grouped_matmul.py docstring has the
    design): the Pallas suite (block-aligned counting-sort dispatch +
    fused GLU kernels — the r4 decomposition's "grouped matmul with
    fused dispatch" lever) on TPU, and the original argsort +
    ``lax.ragged_dot`` path elsewhere / via DSTPU_MOE_KERNEL=xla.
    """
    s, d = xf.shape
    e = p["wg"].shape[0]
    f = p["wg"].shape[-1]
    if _use_pallas_gmm(d, f):
        from jax.ad_checkpoint import checkpoint_name
        from deepspeed_tpu.ops import grouped_matmul as gmm
        bm, bnf, bnd = gmm.pick_blocks(d, f, xf.dtype.itemsize)
        # the counting-sort metadata is tiny (~0.4MB/layer) but its
        # recompute under remat is not (cumsum histogram + int scatters
        # re-run in backward) — name it so the save_* policies keep it
        # cast combine weights to compute dtype BEFORE the dispatch
        # scatter: values are identical to casting after the gather (a
        # scatter moves bits), but the scatter payload halves
        tok, w, g_of_tile, sizes, pos, live = checkpoint_name(
            gmm.aligned_dispatch(topi, topv.astype(xf.dtype), e, bm),
            "moe_dispatch")
        xf1 = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])
        # the sorted-gather is a random-row HBM access pattern — save it
        # (bf16 [R_pad, d], ~74MB/layer at the 16K-token bench) so the
        # remat backward does not re-run it
        xs = checkpoint_name(gmm.gather_rows(xf1, tok, pos), "moe_xs")
        if bm % 128 == 0:
            # combine weights fused into the kernels (w applied in the
            # down kernel, dw computed in the dgdu kernel), the combine
            # below is a residual-free gather-sum, and the backward
            # recomputes gate/up in-kernel from xs — so the layer
            # backward re-runs nothing under any remat policy
            # (ops/grouped_matmul.py module docstring)
            z = gmm.grouped_glu_ffn(
                xs, p["wg"].astype(xs.dtype), p["wi"].astype(xs.dtype),
                p["wo"].astype(xs.dtype), g_of_tile, sizes, live,
                bm=bm, bnf=bnf, bnd=bnd, w=w,
                interpret=jax.default_backend() != "tpu")
            out = gmm.gather_sum(z, tok, pos)
        else:
            # the fused path's lanes-major w tiles need bm % 128 == 0
            # (TPU block rule); tiny-bm geometries (VMEM-shrunk or
            # DSTPU_GMM_BM override) keep the unfused combine
            y = gmm.grouped_glu_ffn(
                xs, p["wg"].astype(xs.dtype), p["wi"].astype(xs.dtype),
                p["wo"].astype(xs.dtype), g_of_tile, sizes, live,
                bm=bm, bnf=bnf, bnd=bnd,
                interpret=jax.default_backend() != "tpu")
            out = gmm.gather_combine(y, w.astype(y.dtype), tok, pos)
    else:
        # stable sort of the S*k (slot, token) assignments by expert id
        flat_e = topi.reshape(-1)                             # [k*S]
        order = jnp.argsort(flat_e, stable=True)              # [k*S]
        tok = order % s                                       # source token
        xs = xf[tok]                                          # [k*S, d]
        group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

        gate_b = lax.ragged_dot(xs, p["wg"].astype(xs.dtype), group_sizes)
        up_b = lax.ragged_dot(xs, p["wi"].astype(xs.dtype), group_sizes)
        hidden = jax.nn.silu(gate_b) * up_b
        out_s = lax.ragged_dot(hidden, p["wo"].astype(xs.dtype),
                               group_sizes)

        w = topv.reshape(-1)[order].astype(xf.dtype)          # [k*S]
        out = jnp.zeros((s, d), xf.dtype).at[tok].add(out_s * w[:, None])

    if "shared" in p:   # dense shared expert, same as the capacity path
        out = out + _shared_expert(p["shared"], xf)
    return out


def dropless_moe_layer(cfg, p, x: jax.Array,
                       top_k: int = 2,
                       aux_loss_coef: float = 0.01,
                       norm_topk: bool = True,
                       ) -> Tuple[jax.Array, jax.Array]:
    """Dropless MoE via sort + ``lax.ragged_dot`` (MegaBlocks-style).

    TPU-native extra beyond the reference (which only has capacity-based
    dispatch, ``sharded_moe.py:_capacity``): no token is ever dropped and
    no capacity padding is computed. Tokens are stably sorted by assigned
    expert, the expert FFN runs as a grouped (ragged) matmul over the
    sorted buffer — ``lax.ragged_dot`` tiles each contiguous group onto
    the MXU — and outputs scatter-add back in token order weighted by
    the gate values. All shapes are static ([S*k]); only ``group_sizes``
    is data-dependent, which ragged_dot consumes as a runtime operand, so
    the whole layer stays jit-compatible.

    Routing math (softmax/top-k/aux) is elementwise and stays wherever
    GSPMD put the tokens; the sort + grouped matmul runs PER DATA SHARD
    inside a shard_map when batch axes are active — a token's output
    never depends on other tokens' grouping, so per-shard grouping is
    exact, and the global argsort's token allgather disappears (it is
    pure overhead, and an unordered collective next to the grad
    allreduce can deadlock XLA's CPU thunk runtime).

    Scope: single expert shard (EP=1). Under EP>1 a dropless all-to-all
    would need dynamic per-shard counts (not jit-static); the capacity
    path (``moe_layer``) is the EP>1 answer, exactly as MegaBlocks is
    single-GPU-group scoped. ``select_moe`` enforces this.
    """
    b, t, d = x.shape
    e = p["router"].shape[-1]
    s = b * t
    xf = x.reshape(s, d)
    # the ENTIRE routing chain runs transposed — [E, S] / [k, S],
    # tokens on lanes. The [S, E] orientation puts E (8ish) on lanes
    # and measured ~2 ms/layer of layout waste at the 16K-token bench
    # (topk_gates_t docstring); the thin matmul below has M=E on
    # sublanes instead of lanes, which XLA tiles fine.
    logits_t = jnp.einsum("de,sd->es", p["router"].astype(jnp.float32),
                          xf.astype(jnp.float32))             # [E,S]
    gates_t = jax.nn.softmax(logits_t, axis=0)                # [E,S]
    topv, topi = topk_gates_t(gates_t, top_k)                 # [k,S]
    if norm_topk:
        topv = topv / jnp.maximum(topv.sum(0, keepdims=True), 1e-9)

    # aux loss — identical formulation to the capacity path (global
    # means over all tokens, GSPMD-reduced)
    mask1_t = (jnp.arange(e, dtype=jnp.int32)[:, None]
               == topi[0][None, :]).astype(jnp.float32)       # [E,S]
    aux = jnp.sum(gates_t.mean(axis=1) * mask1_t.mean(axis=1)) * e

    # routing-health taps (telemetry/health.py): per-expert top-1 load
    # fraction + mean token routing entropy. Static flag on the model
    # config — serving configs never set it, so the 2-tuple return
    # contract of every inference caller is untouched.
    stats = None
    if getattr(cfg, "health_taps", False):
        stats = {"expert_load": mask1_t.mean(axis=1),
                 "router_entropy": -jnp.mean(jnp.sum(
                     gates_t * jnp.log(gates_t + 1e-9), axis=0))}

    batch_axes: Tuple[str, ...] = ()
    from deepspeed_tpu.parallel.mesh import get_mesh, has_mesh
    mesh = get_mesh() if has_mesh() else None
    if mesh is not None:
        batch_axes = tuple(
            a for a in ("data", "data_inner", "expert")
            if a in mesh.shape and mesh.shape[a] > 1)
        bdiv = 1
        for a in batch_axes:
            bdiv *= mesh.shape[a]
        if batch_axes and s % bdiv:
            batch_axes = ()

    if batch_axes:
        ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        spec = P(ax, None)
        spec_t = P(None, ax)    # [k, S] — tokens are the SECOND axis
        fn = jax.shard_map(
            partial(_dropless_ffn, top_k=top_k),
            mesh=mesh, in_specs=(P(), spec, spec_t, spec_t),
            out_specs=spec, axis_names=set(batch_axes), check_vma=False)
        out = fn(p, xf, topv, topi)
    else:
        out = _dropless_ffn(p, xf, topv, topi, top_k)
    if stats is not None:
        return out.reshape(b, t, d), aux * aux_loss_coef, stats
    return out.reshape(b, t, d), aux * aux_loss_coef


#: token count above which dropless beats the capacity dispatch at
#: serving. The no-drop capacity path builds an [S,E,C=S] dispatch mask —
#: O(S²·E) — so its cost grows quadratically with prefill size (measured
#: on a 1.15B 8-expert MoE, one v5e: 2.0x dropless at S=4096, parity at
#: S≈512–2048, slight capacity edge at decode's S=8 where weight
#: streaming dominates and ragged_dot's dynamic grouping breaks fusion).
DROPLESS_MIN_TOKENS = 1024


def serving_moe_fn(model, weight_quant, params, ep: bool):
    """The ONE selection point for both inference engines' ``moe_fn``.

    Serving routes every token deterministically (full capacity, no
    dropping — reference MoE inference EP, inference/engine.py:260).
    Dropless is the fast path for large token counts (linear dispatch
    vs the capacity path's quadratic [S,E,S] mask) but reads raw weight
    leaves, so quantized expert weights (startup ``weight_quant`` OR a
    pre-quantized dstpu_quantize tree) and EP>1 (expert-sharded
    capacity buffers) always use the capacity path's scale-aware
    qmatmul dispatch. Token count is static at trace time, so the
    prefill shapes jit through dropless and the decode shapes through
    capacity — each engine's shape-keyed jit cache keeps both.
    """
    from deepspeed_tpu.inference.engine import _is_quantized_tree
    quantized = bool(weight_quant) or _is_quantized_tree(params)
    capacity_fn = partial(moe_layer, top_k=model.num_experts_per_tok,
                          drop_tokens=False, aux_loss_coef=0.0,
                          ep_axis="expert" if ep else None,
                          norm_topk=model.norm_topk_prob)
    if ep or quantized:
        return capacity_fn
    dropless_fn = partial(dropless_moe_layer,
                          top_k=model.num_experts_per_tok,
                          aux_loss_coef=0.0,
                          norm_topk=model.norm_topk_prob)

    def by_token_count(cfg, p, x, **kw):
        if x.shape[0] * x.shape[1] >= DROPLESS_MIN_TOKENS:
            return dropless_fn(cfg, p, x, **kw)
        return capacity_fn(cfg, p, x, **kw)
    return by_token_count


def moe_layer(cfg, p, x: jax.Array,
              top_k: int = 2,
              capacity_factor: float = 1.0,
              min_capacity: int = 4,
              drop_tokens: bool = True,
              aux_loss_coef: float = 0.01,
              ep_axis: Optional[str] = "expert",
              norm_topk: bool = True,
              rts_key: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """The ``moe_fn`` consumed by models.transformer.decoder_block.

    p: {"router": [d,E], "wg": [E,d,h], "wi": [E,d,h], "wo": [E,h,d]},
    plus optionally "shared" {wg/wi/wo [d,hs]/[hs,d], gate [d,1]} — the
    Qwen2-MoE/DeepSeek shared expert that runs densely on every token.
    x: [B,T,d] → (out [B,T,d], scaled aux loss).
    """
    b, t, d = x.shape
    e = p["router"].shape[-1]
    s = b * t
    xf = x.reshape(s, d)
    logits = jnp.einsum("sd,de->se", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    # drop_tokens=False → static worst-case capacity (reference raises
    # capacity to the max expert load dynamically; shapes must be static
    # under jit, so we provision for S)
    cap = _capacity(s, e, top_k, capacity_factor, min_capacity) \
        if drop_tokens else s
    dispatch, combine, aux = topk_gating(logits, top_k, cap,
                                         norm_probs=norm_topk,
                                         rts_key=rts_key)

    # routing-health taps — see dropless_moe_layer. Load is the top-1
    # assignment fraction from the raw logits (pre-RTS-noise, matching
    # the aux loss's ce term); entropy is the mean token routing entropy.
    stats = None
    if getattr(cfg, "health_taps", False):
        gates = jax.nn.softmax(logits, axis=-1)               # [S,E]
        top1 = jax.nn.one_hot(jnp.argmax(logits, axis=-1), e,
                              dtype=jnp.float32)
        stats = {"expert_load": top1.mean(axis=0),
                 "router_entropy": -jnp.mean(jnp.sum(
                     gates * jnp.log(gates + 1e-9), axis=-1))}

    ep_mesh = None
    if ep_axis is not None:
        from deepspeed_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
        if mesh.shape[ep_axis] > 1:
            ep_mesh = mesh

    # token → expert-buffer regroup; the 'expert' sharding on the E dim
    # makes XLA emit the EP all-to-all (reference _AllToAll:96)
    buf = jnp.einsum("sec,sd->ecd", dispatch.astype(x.dtype), xf)
    if ep_mesh is not None:
        comms_logger.append("all_to_all", buf.size * buf.dtype.itemsize,
                            ep_axis)
        buf = lax.with_sharding_constraint(
            buf, NamedSharding(ep_mesh, P(ep_axis, None, None)))

    # expert FFN (SwiGLU family; per-expert weights on the E dim); a
    # wg_scale leaf (ops/quantized_linear.py suffix convention, attached
    # by the engines' weight_quant config) routes the grouped matmuls
    # through the Pallas batched dequant kernel — int8/fp8 expert
    # weights at half the HBM (serving-only). Under EP>1
    # qmatmul_batched_ep shard_maps the kernel over 'expert' so each
    # shard streams only its local experts' weights (packed int4/fp6
    # stay single-shard, as does the engine guard for them).
    from deepspeed_tpu.ops.quantized_linear import SCALE_SUFFIX
    if "wg" + SCALE_SUFFIX in p:
        from deepspeed_tpu.ops.quantized_linear import qmatmul_batched_ep
        gate = qmatmul_batched_ep(buf, p["wg"], p["wg_scale"],
                                  out_dtype=buf.dtype)
        up = qmatmul_batched_ep(buf, p["wi"], p["wi_scale"],
                                out_dtype=buf.dtype)
        hidden = jax.nn.silu(gate) * up
        out_buf = qmatmul_batched_ep(hidden, p["wo"], p["wo_scale"],
                                     out_dtype=buf.dtype)
    else:
        gate = jnp.einsum("ecd,edh->ech", buf, p["wg"])
        up = jnp.einsum("ecd,edh->ech", buf, p["wi"])
        hidden = jax.nn.silu(gate) * up
        out_buf = jnp.einsum("ech,ehd->ecd", hidden, p["wo"])

    if ep_mesh is not None:
        out_buf = lax.with_sharding_constraint(
            out_buf, NamedSharding(ep_mesh, P(ep_axis, None, None)))

    out = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), out_buf)

    if "shared" in p:   # Qwen2-MoE/DeepSeek: dense expert on every token
        out = out + _shared_expert(p["shared"], xf)
    if stats is not None:
        return out.reshape(b, t, d), aux * aux_loss_coef, stats
    return out.reshape(b, t, d), aux * aux_loss_coef


# ---------------------------------------------------------------------------
# The expert-parallel SHARE and its routers (serving)
# ---------------------------------------------------------------------------

#: capacity of one round of the share's dispatch: rows an expert's buffer
#: holds (one MXU tile of rows). Up to this many tokens the held experts
#: simply compute every token (the weights' bytes bound a decode step, not
#: the rows); beyond, a held assignment takes the next free row of its
#: expert's buffer in token order, and the buffers are served in rounds
HELD_ROUND_ROWS = 128


@jax.named_scope("moe_router")
def route_tokens(cfg, p, xf: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The share's router over ALL ``cfg.num_experts``: xf [S, d] →
    (weights [S, k] float32, expert ids [S, k] int32, best first), by
    ``cfg.router_scoring``:

    - ``"sigmoid"`` (DeepSeek-V3 / MiMo-V2: a score per expert).
      ``p["router_bias"]`` (``router_select_bias``, ``noaux_tc``) is added
      for the SELECTION only: the weights are the unbiased scores.
      ``router_groups`` > 1 (``n_group`` / ``topk_group``): the picks in
      equal groups, a group scored by the sum of its two highest, the picks
      outside the ``router_groups_kept`` best groups set to 0.0 before the
      top-k (as HF's ``deepseek_v3`` gate masks them). ``norm_topk_prob``:
      the kept weights over their sum + ``cfg.router_norm_eps`` (1e-20;
      LFM2's gate adds 1e-6).
    - ``"softmax"``: the ``k`` largest LOGITS are kept; with
      ``norm_topk_prob`` the weights are the softmax over THOSE ``k``
      logits alone (Granite's gate; Mixtral's softmax over all,
      renormalised over the kept, is the same numbers), without it the
      kept entries of the softmax over all experts. No bias, no groups.

    ``routed_scale`` multiplies the weights. float32 throughout, as the
    published gates are (a bf16 logit flips a near-tied selection, and a
    flipped expert is a different token): the input is upcast, the matmul
    runs at ``Precision.HIGHEST`` (on a TPU a float32 matmul is otherwise
    bf16 passes). (The uniform stack's capacity router is
    :func:`moe_layer`'s own; it has no share.)"""
    if cfg.router_scoring not in ("sigmoid", "softmax"):
        raise NotImplementedError(
            f"route_tokens builds router_scoring 'sigmoid' and 'softmax'; "
            f"got {cfg.router_scoring!r}")
    logits = jnp.einsum(
        "sd,de->se", xf.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    if cfg.router_scoring == "softmax":
        if "router_bias" in p or cfg.router_groups > 1:
            raise NotImplementedError(
                "a softmax router takes no selection bias and no groups")
        kept, topi = lax.top_k(logits, cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            topw = jax.nn.softmax(kept, axis=-1)
        else:
            topw = jnp.exp(kept - jax.nn.logsumexp(logits, axis=-1,
                                                   keepdims=True))
    else:
        scores = jax.nn.sigmoid(logits)
        pick = scores
        if "router_bias" in p:
            pick = scores + p["router_bias"].astype(jnp.float32)
        if cfg.router_groups > 1:
            grouped = pick.reshape(pick.shape[0], cfg.router_groups, -1)
            _, kept = lax.top_k(lax.top_k(grouped, 2)[0].sum(-1),
                                cfg.router_groups_kept)        # [S, kept]
            in_kept = jax.nn.one_hot(
                kept, cfg.router_groups,
                dtype=jnp.bool_).any(axis=1)                   # [S, groups]
            pick = jnp.where(in_kept[..., None], grouped,
                             0.0).reshape(pick.shape)
        _, topi = lax.top_k(pick, cfg.num_experts_per_tok)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.norm_topk_prob:
            topw = topw / (topw.sum(-1, keepdims=True) + cfg.router_norm_eps)
    if cfg.routed_scale != 1.0:
        topw = topw * cfg.routed_scale
    return topw, topi.astype(jnp.int32)


@jax.named_scope("moe_experts")
def _held_glu(p, buf: jax.Array) -> jax.Array:
    """buf [H, C, d] → [H, C, d]: each held expert's SiLU-GLU on its rows;
    a tree with no ``wg`` holds un-gated ``relu²`` experts, two matrices
    each: ``relu(x·wi)²·wo``."""
    if "wg" not in p:
        return jnp.einsum("ech,ehd->ecd", _relu2(jnp.einsum(
            "ecd,edh->ech", buf, p["wi"])), p["wo"])
    gate = jnp.einsum("ecd,edh->ech", buf, p["wg"])
    up = jnp.einsum("ecd,edh->ech", buf, p["wi"])
    return jnp.einsum("ech,ehd->ecd", jax.nn.silu(gate) * up, p["wo"])


@jax.jit
def _held_rounds(p, xf: jax.Array, topw: jax.Array, local: jax.Array,
                 mine: jax.Array) -> jax.Array:
    """The many-token form of :func:`held_experts_moe_layer`: xf [S, d] in
    the experts' dtype, the router's weights ``topw`` [S, k] float32, the
    picks as HELD experts' indices ``local`` [S, k] and which of them count
    (``mine``: held here and a real token) → [S, d] float32. A jit of its
    own: a stack's layers of one shape are traced and lowered ONCE."""
    (s, d), k, held = xf.shape, topw.shape[1], p["wo"].shape[0]
    cap = HELD_ROUND_ROWS
    # a token picks an expert at most once: its PLACE in that expert's rows
    # is the number of earlier tokens that picked it too
    experts = jnp.arange(held, dtype=jnp.int32)
    picks = mine[..., None] & (local[..., None] == experts)    # [S, k, H]
    upto = jnp.cumsum(picks.any(axis=1).astype(jnp.int32), axis=0)  # [S, H]
    sizes = upto[-1]                                           # [H]
    # pick-major from here on: a pick's rows of all tokens lie together
    place = jnp.sum(jnp.where(picks, (upto - 1)[:, None, :], 0),
                    axis=-1).T                                 # [k, S]
    local_t, mine_t, topw_t = local.T, mine.T, topw.T
    slot = jnp.arange(cap, dtype=jnp.int32)

    def one_round(r, out):
        # the token in slot c of expert e: the first whose count reaches
        # r·cap + c + 1, i.e. how many tokens' counts are still under it
        # (a slot past the expert's last row: a token that is never read)
        tok = jnp.sum(upto.T[:, None, :] <= (r * cap + slot)[None, :, None],
                      axis=-1, dtype=jnp.int32)                # [H, cap]
        y = _held_glu(p, xf[jnp.minimum(tok, s - 1)])          # [H, cap, d]
        # each token reads ITS rows back, best pick first
        at = place - r * cap
        here = mine_t & (at >= 0) & (at < cap)                 # [k, S]
        rows = y.reshape(held * cap, d)[
            jnp.where(here, local_t * cap + at, 0)]            # [k, S, d]
        for j in range(k):
            out = out + jnp.where(
                here[j][:, None],
                rows[j].astype(jnp.float32) * topw_t[j][:, None], 0.0)
        return out

    # round 0 is straight-line code: one round serves every expert unless
    # routing is badly skewed; the loop is entered for the rest alone
    out = one_round(0, jnp.zeros((s, d), jnp.float32))
    rounds = (jnp.max(sizes) + cap - 1) // cap
    out = lax.fori_loop(1, rounds, one_round, out)
    return out


def held_experts_moe_layer(cfg, p, x: jax.Array,
                           valid: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """A ``moe_fn`` for an expert layer that is TOLD which experts it
    holds (``cfg.experts_held`` = (first, count); None = all): it routes
    every token over all ``cfg.num_experts``, and computes the part of
    the result that its own experts give. Assignments to absent experts
    are dropped before any buffer is built; what those experts would have
    added is left out (on their chips it is computed, and an exchange
    this layer does not have sums the parts). One chip: no collective.

    p: ``router [d, E]``, optionally ``router_bias [E]``, and the HELD
    experts' ``wg / wi [H, d, f]``, ``wo [H, f, d]`` (no ``wg``: un-gated
    ``relu²`` experts, :func:`_held_glu`). x [B, T, d];
    ``valid`` [B, T] bool marks real tokens (padding slots of a packed
    step are dropped like absent experts). No token of a held expert is
    ever dropped: up to ``HELD_ROUND_ROWS`` tokens every held expert
    computes every token (weights bound that shape); beyond, an
    assignment's place in its expert's rows is the number of earlier
    tokens that picked the expert (a prefix sum down the tokens: the
    order a stable sort by expert would give, without the sort), and the
    experts are served ``HELD_ROUND_ROWS`` rows each at a time, in as
    many rounds as the fullest expert needs. A round moves rows by
    gathers alone — the buffers' rows by a table of token ids, each
    token's own ``k`` rows back, weighted and summed in float32, best
    pick first — so no destination repeats and two calls give the same
    bits. The first round is straight-line code (one round serves every
    expert unless routing is badly skewed: a layer is then no loop to
    the scheduler); the rest are a loop that is entered only when some
    expert has more than ``HELD_ROUND_ROWS`` rows. Returns (out, 0.0):
    serving has no balance loss."""
    b, t, d = x.shape
    s = b * t
    first, held = cfg.experts_held or (0, cfg.num_experts)
    k = cfg.num_experts_per_tok
    xf = x.reshape(s, d)
    topw, topi = route_tokens(cfg, p, xf)     # x as it is (float32 stream)
    xf = xf.astype(p["wo"].dtype)             # the experts' compute dtype
    local = topi - first                                       # [S, k]
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid.reshape(s, 1)
    zero = jnp.zeros((), jnp.float32)

    if s <= HELD_ROUND_ROWS:
        # every held expert on every token; the combine weights carry the
        # routing (0 where a token did not pick the expert)
        comb = jnp.sum(
            jnp.where(mine[..., None],
                      topw[..., None] * jax.nn.one_hot(
                          local, held, dtype=jnp.float32), 0.0),
            axis=1)                                            # [S, H]
        y = _held_glu(p, jnp.broadcast_to(xf[None], (held, s, d)))
        out = jnp.einsum("esd,se->sd", y, comb,
                         preferred_element_type=jnp.float32)
        return out.astype(x.dtype).reshape(b, t, d), zero

    out = _held_rounds(p, xf, topw, local, mine)
    return out.astype(x.dtype).reshape(b, t, d), zero
