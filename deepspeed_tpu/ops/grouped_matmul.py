"""Pallas grouped (expert-ragged) matmul suite for dropless MoE.

The TPU answer to the reference's grouped-GEMM MoE kernels
(``inference/v2/kernels/cutlass_ops/moe_gemm/`` — CUTLASS grouped GEMM over
per-expert problem sizes; training-side dispatch in
``moe/sharded_moe.py``). Three design moves, none of which translate from
the CUDA implementation:

**Block-aligned dropless dispatch.** MegaBlocks-style grouped kernels pay
for tiles that straddle expert boundaries (per-tile group metadata, masked
accumulation, output revisiting). Instead we pad each expert's row range up
to the kernel's m-tile size when building the sorted layout, so every
m-tile belongs to EXACTLY one expert: the only per-tile metadata is one
scalar-prefetched ``group_of_tile`` vector consumed by the weight
BlockSpec index maps, and the matmul body is a plain dense tile. Expected
padding cost is ``E·bm/2`` rows (~3% of a 32K-row batch at bm=256) —
measured far below the straddle-tile machinery it replaces
(``megablox.gmm`` benched 2.4x slower than even ``lax.ragged_dot`` on
v5e, docs/kernels.md).

**Counting-sort dispatch, no argsort.** The (token, slot)→position map is
a cumulative histogram (one [S·k, E] cumsum) instead of a 32K-element
argsort — TPU sorts are lane-serial and measurably dominate the dispatch
cost the r4 decomposition attributed to "sort/gather/scatter".

**Fused GLU matmuls.** One kernel computes gate AND up projections per LHS
fetch (halving activation reads for the first two matmuls); the down
kernel recomputes ``silu(gate)·up`` from the saved pre-activations in its
epilogue, so the [R, ffn] hidden tensor is never materialized in HBM.

**All-Pallas backward.** The custom VJP keeps every backward matmul in
Pallas: dgate/dup with the dH product AND the dwo outer product fused
into one kernel (gate/up/dY stream through VMEM once); dxs as a dual
full-K grouped matmul on the weights' native layouts (no transposed
weight copies in HBM); dwg/dwi as grouped outer products whose running
sums live in VMEM scratch and write each expert's f32 block exactly once
(accumulating into out_ref round-trips the block through HBM every
step). ``DSTPU_GMM_DW=ragged`` falls back to ``lax.ragged_dot_general``
for the weight grads — exact over the aligned layout because padding
rows carry zero activations and zero gradients.

**Gather-only dispatch.** Counting sort yields BOTH permutation
directions, so dispatch and combine are pure gathers in fwd and bwd
(:func:`gather_rows` / :func:`gather_combine`) — TPU row scatter-adds
serialize per index.

**Fused combine weights (r5).** Passing ``w`` to :func:`grouped_glu_ffn`
applies the per-row combine weights INSIDE the down kernel and computes
their gradient (``dw[r] = dZ[r]·y[r]``, the router's training signal)
inside the dgdu kernel as a per-f-tile ``rowsum(dh·h)`` — both already
have the operands streaming through VMEM. The combine then collapses to
the residual-free :func:`gather_sum`: no ``[R,d]`` elementwise scale in
fwd or bwd, no separate ``[R,d]`` row-dot for ``dw``.

**Residual-free backward (r5).** The scaled path's dgdu kernel
(:func:`_dgdu_rc_kernel`) recomputes the GLU pre-activations in-kernel
from ``xs``, so the VJP residuals carry NO ``[R, f]`` tensors at all:
under any remat policy the layer backward re-runs zero kernels, and
gate/up never round-trip HBM in the backward (the old path either
re-ran the gate_up kernel — writing 2×[R,f] that dgdu then re-read —
or stacked 4.7 GB of ``moe_glu`` residuals across the layer scan,
which measured SLOWER than the re-run).

Parity is asserted against a per-expert einsum reference in
tests/test_grouped_matmul.py; integration (full dropless layer fwd+bwd vs
the ragged_dot path, including router gradients) in tests/test_moe.py.
Measured on the r5 1B/8e bench: 26.3% → 35.9% active-param MFU.
"""

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["aligned_dispatch", "grouped_glu_ffn", "gather_rows",
           "gather_combine", "gather_sum", "supported", "pick_blocks"]

_LANE = 128
_VMEM_BUDGET = 12 * 2**20   # double-buffered per-step bytes we allow


# ---------------------------------------------------------------------------
# dispatch metadata
# ---------------------------------------------------------------------------

def aligned_dispatch(topi: jax.Array, topv: jax.Array, num_experts: int,
                     bm: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array, jax.Array, jax.Array]:
    """Counting-sort (token, slot) assignments into a block-aligned layout.

    topi/topv: [k, S] expert ids / combine weights, SLOT-MAJOR (the
    whole routing chain runs transposed — tokens on lanes; see
    ``topk_gates_t``). Returns:

    - ``sorted_tok`` [R_pad] int32 — source token for each sorted row;
      padding rows hold the sentinel ``S`` (callers gather from an
      ``xf`` with a zero row appended at index S).
    - ``sorted_w`` [R_pad] — combine weight per sorted row, 0 on padding.
      Differentiable w.r.t. ``topv`` (the only float input).
    - ``group_of_tile`` [R_pad // bm] int32 — owning expert per m-tile.
    - ``sizes_padded`` [E] int32 — per-expert row count INCLUDING its
      alignment padding; the last entry also absorbs the dead tail up
      to R_pad, whose rows the kernels SKIP and leave unspecified (the
      ragged dw fallback zero-masks them before reducing).
    - ``pos`` [k, S] int32 — the INVERSE map: row index of each (slot,
      token) assignment in the sorted layout. Having both directions
      lets dispatch AND combine run as pure gathers in both fwd and bwd
      (:func:`gather_rows` / :func:`gather_combine`) — TPU row
      scatter-adds serialize and measured far slower than gathers.
      ``pos[slot]`` is a clean [S] lanes-major vector per slot.
    - ``live_tiles`` [1] int32 — number of m-tiles containing aligned
      content; every kernel skips tiles at/past it, so rows beyond
      ``live_tiles*bm`` are UNSPECIFIED in all produced arrays.

    All shapes are static: R_pad = round_up(S·k, bm) + E·bm bounds the
    aligned total for any routing.
    """
    k, s = topi.shape
    r0 = s * k
    e = num_experts
    r_pad = _round_up(r0, bm) + e * bm
    flat_e = topi.reshape(-1).astype(jnp.int32)      # [R0] slot-major
    # transposed [E, R0] histogram: E lives on SUBLANES and R0 on lanes,
    # so the running-count cumsum vectorizes over full 128-lane tiles —
    # the [R0, E] orientation used 8 of 128 lanes and profiled at
    # ~0.5ms/layer on the 16K-token bench
    onehot_t = (flat_e[None, :] ==
                jnp.arange(e, dtype=jnp.int32)[:, None]).astype(jnp.int32)
    cum_t = jnp.cumsum(onehot_t, axis=1)                      # [E, R0]
    counts = cum_t[:, -1]                                     # [E]
    # aligned starts: each group begins on an m-tile boundary. Every
    # expert gets AT LEAST one tile (all-sentinel when empty): the dw
    # kernels zero-init each group's output blocks on first visit, so an
    # expert with no tiles would return uninitialized memory as its
    # weight gradient.
    aligned = jnp.maximum(_round_up_arr(counts, bm), bm)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(aligned)[:-1].astype(jnp.int32)])
    # rank of each assignment within its expert = exclusive running count
    rank = jnp.take_along_axis(cum_t, flat_e[None, :],
                               axis=0)[0] - 1                 # [R0]
    pos = starts[flat_e] + rank                               # [R0]
    tok = (jnp.arange(r0, dtype=jnp.int32) % s)               # source token
    # pos is a permutation into [0, r_pad) — tell XLA (unique + in
    # bounds) so the TPU scatter lowering can skip the serializing
    # duplicate-combine path
    sorted_tok = jnp.full((r_pad,), s, jnp.int32).at[pos].set(
        tok, unique_indices=True, mode="promise_in_bounds")
    sorted_w = jnp.zeros((r_pad,), topv.dtype).at[pos].set(
        topv.reshape(-1), unique_indices=True, mode="promise_in_bounds")
    nm = r_pad // bm
    tile_starts = jnp.arange(nm, dtype=jnp.int32) * bm
    group_of_tile = (jnp.searchsorted(starts, tile_starts, side="right")
                     .astype(jnp.int32) - 1)
    # last group's padded size absorbs the tail tiles beyond the data
    ends = jnp.concatenate([starts[1:], jnp.array([r_pad], jnp.int32)])
    sizes_padded = (ends - starts).astype(jnp.int32)
    # tiles past the aligned content are pure sentinel — the kernels
    # skip their compute entirely (R_pad is a worst-case STATIC bound;
    # the average waste it would cost is ~E*bm/2 rows of matmul)
    live_tiles = (jnp.sum(aligned) // bm).astype(jnp.int32)[None]
    return (sorted_tok, sorted_w, group_of_tile, sizes_padded,
            pos.reshape(k, s), live_tiles)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _round_up_arr(x: jax.Array, m: int) -> jax.Array:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# gather-only dispatch / combine
#
# TPU row scatter-adds serialize per index; the counting-sort layout gives
# BOTH permutation directions up front, so each direction's VJP is
# expressed with the opposite gather — no [R, d] scatter anywhere in the
# layer, fwd or bwd.
# ---------------------------------------------------------------------------

@jax.custom_vjp
def gather_rows(xf1: jax.Array, sorted_tok: jax.Array,
                pos: jax.Array) -> jax.Array:
    """xs[r] = xf1[sorted_tok[r]] — dispatch gather into sorted order.

    xf1 [S+1, d] (a zero sentinel row appended at index S), sorted_tok
    [R_pad], pos [k, S]. The VJP accumulates via the inverse gather:
    dxf1[t] = Σ_slot dxs[pos[slot, t]]; the sentinel row's gradient is
    dropped (callers append a constant zero row, whose gradient the
    enclosing concat discards anyway).
    """
    return xf1[sorted_tok]


def _gather_rows_fwd(xf1, sorted_tok, pos):
    return xf1[sorted_tok], (pos, sorted_tok.shape)


def _gather_rows_bwd(res, dxs):
    pos, tok_shape = res
    # k unrolled gathers + adds, NOT dxs[pos].sum(0): the [k, S, d]
    # intermediate and its reduce was one of the profiled per-layer
    # hot spots
    dxf = dxs[pos[0]]
    for slot in range(1, pos.shape[0]):
        dxf = dxf + dxs[pos[slot]]
    dxf1 = jnp.concatenate([dxf, jnp.zeros((1, dxs.shape[-1]), dxs.dtype)])
    return (dxf1, np.zeros(tok_shape, jax.dtypes.float0),
            np.zeros(pos.shape, jax.dtypes.float0))


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def gather_combine(y: jax.Array, w: jax.Array, sorted_tok: jax.Array,
                   pos: jax.Array) -> jax.Array:
    """out[t] = Σ_slot w[pos[slot,t]] · y[pos[slot,t]] — the combine as a
    gather over the inverse map instead of a scatter-add over tokens.

    y [R_pad, d], w [R_pad] (zero on padding rows), pos [k, S] →
    out [S, d]. Differentiable in y AND w (w carries the router's gate
    values, so its gradient trains the router).
    """
    return _combine_impl(y, w, pos)


def _combine_impl(y, w, pos):
    # k unrolled gathers + adds (see _gather_rows_bwd for why)
    yw = y * w[:, None].astype(y.dtype)
    out = yw[pos[0]]
    for slot in range(1, pos.shape[0]):
        out = out + yw[pos[slot]]
    return out


def _gather_combine_fwd(y, w, sorted_tok, pos):
    return _combine_impl(y, w, pos), (y, w, sorted_tok, pos.shape)


def _gather_combine_bwd(res, dout):
    y, w, sorted_tok, pos_shape = res
    dout1 = jnp.concatenate(
        [dout, jnp.zeros((1, dout.shape[-1]), dout.dtype)])
    d_rows = dout1[sorted_tok]                                # [R_pad, d]
    dy = d_rows * w[:, None].astype(d_rows.dtype)
    if os.environ.get("DSTPU_GMM_DCOMBINE") == "zero":
        # BENCH-ONLY diagnostic: skip the combine-weight gradient (cuts
        # the router's training signal) to expose its cost
        dw = jnp.zeros_like(w)
    else:
        dw = jnp.sum(d_rows.astype(jnp.float32) * y.astype(jnp.float32),
                     axis=-1).astype(w.dtype)
    return (dy, dw, np.zeros(sorted_tok.shape, jax.dtypes.float0),
            np.zeros(pos_shape, jax.dtypes.float0))


gather_combine.defvjp(_gather_combine_fwd, _gather_combine_bwd)


# ---------------------------------------------------------------------------
# block-size selection
# ---------------------------------------------------------------------------

def _block(dim: int, target: int) -> int:
    """min(dim rounded up to a lane multiple, target). Blocks need NOT
    divide the dim — grids use cdiv and Pallas masks the edge blocks
    (partial reads only ever feed lanes whose outputs are also masked)."""
    return min(_round_up(dim, _LANE), target)


def pick_blocks(d: int, f: int, itemsize: int = 2
                ) -> Tuple[int, int, int]:
    """(bm, bnf, bnd) for the kernel suite, shrunk to the VMEM budget.

    Env overrides: DSTPU_GMM_BM / DSTPU_GMM_BNF / DSTPU_GMM_BND govern
    the forward kernels; the backward kernels size their own tiles
    (DSTPU_GMM_BNF_BWD in :func:`_dgdu_rc`, DSTPU_GMM_BND_BWD in
    :func:`_dxs`).
    """
    # forward-kernel tiles (the backward sizes its own: _dgdu_rc /
    # _dxs). bnf=1024 from the r5 trace: gate_up measured 3.1 ms/layer
    # there vs 4.9 at 256 on the 1B/8e bench (the 256 sweep win predated
    # the backward's independent knobs); bm > 256 fails to compile
    bnf_env = int(os.environ.get("DSTPU_GMM_BNF", 0))
    bnf = _block(f, bnf_env or 1024)
    bnd = _block(d, int(os.environ.get("DSTPU_GMM_BND", 512)))
    bm = int(os.environ.get("DSTPU_GMM_BM", 0)) or 256
    # dominant per-step footprint (gate_up kernel): xs + 2 weight blocks +
    # 2 out blocks, double-buffered. The 2·d·bnf weight term is
    # bm-INDEPENDENT, so big-d geometries must shrink bnf first (an
    # explicit env bnf is honored as given); bm shrinks last.
    step = lambda: (bm * d + 2 * d * bnf + 2 * bm * bnf) * itemsize * 2
    if not bnf_env:
        while bnf > 256 and step() > _VMEM_BUDGET:
            bnf //= 2
    while bm > 16 and step() > _VMEM_BUDGET:
        bm //= 2
    if bnf_env and step() > _VMEM_BUDGET:
        # auto-sizing silently degrades; an explicit pin that cannot fit
        # even at the floor bm must fail loudly instead of OOMing VMEM
        # deep inside Mosaic with an unrelated-looking error
        raise ValueError(
            f"DSTPU_GMM_BNF={bnf_env} needs {step()} bytes of VMEM for "
            f"the gate_up tiles at d={d} (> {_VMEM_BUDGET} budget) even "
            f"at bm={bm}; lower the override")
    return bm, bnf, bnd


def supported(d: int, f: int) -> bool:
    """Shape gate: both matmul dims must tile to the 128-lane rule."""
    return d % _LANE == 0 and f % _LANE == 0


# ---------------------------------------------------------------------------
# kernels — grid (n_tiles, m_tiles), m innermost: group_of_tile is
# monotone in m, so weight blocks refetch only on expert transitions
# ---------------------------------------------------------------------------

def _gate_up_kernel(g_ref, lt_ref, xs_ref, wg_ref, wi_ref, gate_ref,
                    up_ref):
    @pl.when(pl.program_id(1) < lt_ref[0])
    def _():
        xs = xs_ref[...]
        gate_ref[...] = jnp.dot(xs, wg_ref[0],
                                preferred_element_type=jnp.float32
                                ).astype(gate_ref.dtype)
        up_ref[...] = jnp.dot(xs, wi_ref[0],
                              preferred_element_type=jnp.float32
                              ).astype(up_ref.dtype)


def _down_kernel(g_ref, lt_ref, gate_ref, up_ref, wo_ref, y_ref):
    @pl.when(pl.program_id(1) < lt_ref[0])
    def _():
        g32 = gate_ref[...].astype(jnp.float32)
        u32 = up_ref[...].astype(jnp.float32)
        h = (jax.nn.silu(g32) * u32).astype(wo_ref.dtype)
        y_ref[...] = jnp.dot(h, wo_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)


def _down_w_kernel(g_ref, lt_ref, gate_ref, up_ref, w_ref, wo_ref, z_ref):
    """Down projection with the per-row combine weight fused into the
    epilogue: Z = diag(w)·(silu(gate)·up)·wo[g]. ``w_ref`` is a
    lanes-major (1, bm) tile row (the flash kernels' lse layout)."""
    @pl.when(pl.program_id(1) < lt_ref[0])
    def _():
        g32 = gate_ref[...].astype(jnp.float32)
        u32 = up_ref[...].astype(jnp.float32)
        h = (jax.nn.silu(g32) * u32).astype(wo_ref.dtype)
        y = jnp.dot(h, wo_ref[0], preferred_element_type=jnp.float32)
        w = w_ref[0, 0].astype(jnp.float32)                  # [bm] lanes
        z_ref[...] = (y * w[:, None]).astype(z_ref.dtype)


def _dgdu_kernel(g_ref, lt_ref, dy_ref, wo_ref, gate_ref, up_ref,
                 dg_ref, du_ref, dwo_ref, acc_o):
    """dH = dY·wo[g]^T (contracted on wo's own [f, d] layout — no
    transposed weight copy in HBM); dgate/dup epilogue; PLUS the dwo
    outer product — gate/up/dY are already streaming through VMEM here,
    so dwo costs one extra dot instead of a whole kernel's HBM re-sweep.
    Accumulates in VMEM scratch, written once per group (see
    _dw_pair_kernel for why not out_ref)."""
    i = pl.program_id(1)
    nm = pl.num_programs(1)
    live = lt_ref[0]

    @pl.when(i < live)
    def _():
        first = jnp.logical_or(
            i == 0, g_ref[i] != g_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            acc_o[...] = jnp.zeros_like(acc_o)

        dy = dy_ref[...]
        dh = lax.dot_general(dy, wo_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        g32 = gate_ref[...].astype(jnp.float32)
        u32 = up_ref[...].astype(jnp.float32)
        sg = jax.nn.sigmoid(g32)
        silu_g = g32 * sg
        dsilu = sg * (1.0 + g32 * (1.0 - sg))
        dg_ref[...] = (dh * u32 * dsilu).astype(dg_ref.dtype)
        du_ref[...] = (dh * silu_g).astype(du_ref.dtype)
        h = (silu_g * u32).astype(dy.dtype)
        acc_o[...] += lax.dot_general(
            h, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        # the LAST live tile flushes group E-1 (dead tiles never run)
        last = jnp.logical_or(
            i + 1 >= live, g_ref[i] != g_ref[jnp.minimum(i + 1, nm - 1)])

        @pl.when(last)
        def _():
            dwo_ref[0] = acc_o[...]


def _dgdu_rc_kernel(g_ref, lt_ref, dz_ref, w_ref, xs_ref, wg_ref, wi_ref,
                    wo_ref, dg_ref, du_ref, dwo_ref, dwp_ref, acc_o, *,
                    f_total, bnf):
    """The scaled-FFN backward tile with the GLU pre-activations
    RECOMPUTED in-kernel from ``xs`` instead of read from HBM.

    Upstream dZ arrives UNSCALED by the combine weights (the combine is
    a plain gather-sum), so this kernel additionally produces the
    combine-weight gradient ``dw[r] = dZ[r]·y[r] = Σ_f dh[r,f]·h[r,f]``
    as per-f-tile partials (``dwp_ref``; summed over f-tiles by the
    caller), and dgate/dup/dwo pick up the per-row w factor
    (``d(h·wo) = w ⊙ dZ``).

    This removes the remat re-run of the gate_up kernel from the layer
    backward entirely: the scaled FFN's VJP residuals are just
    (xs, w, weights, dispatch metadata) — xs is already kept by the
    ``moe_xs`` save — so under ANY remat policy the backward re-runs
    nothing and gate/up never round-trip HBM in the backward (the
    re-run wrote 2×[R,f] and this kernel re-read them; both gone for
    the cost of streaming xs once per f-tile). Grid (n_f, n_m), m
    innermost; wg/wi blocks ride the existing expert-monotone index
    maps so they refetch only on transitions."""
    i = pl.program_id(1)
    nm = pl.num_programs(1)
    j = pl.program_id(0)
    live = lt_ref[0]

    @pl.when(i < live)
    def _():
        first = jnp.logical_or(
            i == 0, g_ref[i] != g_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            acc_o[...] = jnp.zeros_like(acc_o)

        dz = dz_ref[...]
        w32 = w_ref[0, 0].astype(jnp.float32)                # [bm] lanes
        xs = xs_ref[...]
        # recompute this f-tile's gate/up (bitwise the forward kernel's
        # math: bf16 operands, f32 MXU accumulation, cast back)
        g32 = jnp.dot(xs, wg_ref[0],
                      preferred_element_type=jnp.float32)
        u32 = jnp.dot(xs, wi_ref[0],
                      preferred_element_type=jnp.float32)
        g32 = g32.astype(dz.dtype).astype(jnp.float32)
        u32 = u32.astype(dz.dtype).astype(jnp.float32)
        dh = lax.dot_general(dz, wo_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        sg = jax.nn.sigmoid(g32)
        silu_g = g32 * sg
        h32 = silu_g * u32
        if f_total % bnf:
            col = lax.broadcasted_iota(jnp.int32, h32.shape, 1)
            valid = (col + j * bnf) < f_total
            prod = jnp.where(valid, dh * h32, 0.0)
        else:
            prod = dh * h32
        dwp_ref[0, 0, 0, :] = jnp.sum(prod, axis=1)
        dhw = dh * w32[:, None]
        dsilu = sg * (1.0 + g32 * (1.0 - sg))
        dg_ref[...] = (dhw * u32 * dsilu).astype(dg_ref.dtype)
        du_ref[...] = (dhw * silu_g).astype(du_ref.dtype)
        h = h32.astype(dz.dtype)
        dzw = (dz.astype(jnp.float32) * w32[:, None]).astype(dz.dtype)
        acc_o[...] += lax.dot_general(
            h, dzw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        last = jnp.logical_or(
            i + 1 >= live, g_ref[i] != g_ref[jnp.minimum(i + 1, nm - 1)])

        @pl.when(last)
        def _():
            dwo_ref[0] = acc_o[...]


def _dxs_kernel(g_ref, lt_ref, dg_ref, du_ref, wg_ref, wi_ref, dxs_ref):
    # contract f on the weights' native [d, f] layout (wg block is
    # (1, bnd, f) — a d-slice), avoiding transposed HBM weight copies
    @pl.when(pl.program_id(1) < lt_ref[0])
    def _():
        acc = lax.dot_general(dg_ref[...], wg_ref[0],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        acc += lax.dot_general(du_ref[...], wi_ref[0],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        dxs_ref[...] = acc.astype(dxs_ref.dtype)


def _dw_pair_kernel(g_ref, lt_ref, xs_ref, dg_ref, du_ref, dwg_ref,
                    dwi_ref, acc_g, acc_i):
    """Grouped outer products dwg[e] = Σ xs^T dg, dwi[e] = Σ xs^T du.

    Grid (n_f_tiles, n_m_tiles), m innermost: g[i] is monotone in i, so
    each (expert, j) output block is owned by ONE consecutive run of
    steps. The running sums live in VMEM *scratch* and the output block
    is written exactly once, on the group's last tile — accumulating
    into out_ref directly round-trips the 4MB f32 block through HBM
    every step (measured 10% MXU efficiency vs ~2ms ideal)."""
    i = pl.program_id(1)
    nm = pl.num_programs(1)
    live = lt_ref[0]

    @pl.when(i < live)
    def _():
        first = jnp.logical_or(
            i == 0, g_ref[i] != g_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            acc_g[...] = jnp.zeros_like(acc_g)
            acc_i[...] = jnp.zeros_like(acc_i)

        xs = xs_ref[...]
        acc_g[...] += lax.dot_general(
            xs, dg_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_i[...] += lax.dot_general(
            xs, du_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        last = jnp.logical_or(
            i + 1 >= live, g_ref[i] != g_ref[jnp.minimum(i + 1, nm - 1)])

        @pl.when(last)
        def _():
            dwg_ref[0] = acc_g[...]
            dwi_ref[0] = acc_i[...]


def _dw_pair(xs, dg, du, g_of_tile, live_tiles, num_experts, bm,
             interpret):
    """→ (dwg, dwi) [E, d, f] f32."""
    r_pad, d = xs.shape
    f = dg.shape[-1]
    bnf = max(_LANE, min(512, _round_up(f, _LANE)))
    grid = (pl.cdiv(f, bnf), r_pad // bm)
    specs = [
        pl.BlockSpec((bm, d), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
    ]
    out_specs = [pl.BlockSpec((1, d, bnf), lambda j, i, g, lt: (g[i], 0, j))] * 2
    shape = [jax.ShapeDtypeStruct((num_experts, d, f), jnp.float32)] * 2
    scratch = [pltpu.VMEM((d, bnf), jnp.float32)] * 2
    return _grid_call(_dw_pair_kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, xs, dg, du,
                      scratch=scratch)


def _grid_call(kernel, grid, in_specs, out_specs, out_shape, interpret,
               group_of_tile, live_tiles, *args, scratch=None):
    # the kernel's name is the HLO instruction's in a device trace:
    # gmm_gate_up, gmm_down, gmm_down_w, gmm_dgdu, gmm_dgdu_rc, gmm_dxs,
    # gmm_dw_pair
    body = getattr(kernel, "func", kernel).__name__
    return pl.pallas_call(
        kernel,
        name="gmm" + body.removesuffix("_kernel"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch or []),
        out_shape=out_shape,
        interpret=interpret,
    )(group_of_tile, live_tiles, *args)


def _gate_up(xs, wg, wi, g_of_tile, live_tiles, bm, bnf, interpret):
    r_pad, d = xs.shape
    f = wg.shape[-1]
    grid = (pl.cdiv(f, bnf), r_pad // bm)
    specs = [
        pl.BlockSpec((bm, d), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, d, bnf), lambda j, i, g, lt: (g[i], 0, j)),
        pl.BlockSpec((1, d, bnf), lambda j, i, g, lt: (g[i], 0, j)),
    ]
    out_specs = [pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j))] * 2
    shape = [jax.ShapeDtypeStruct((r_pad, f), xs.dtype)] * 2
    return _grid_call(_gate_up_kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, xs, wg, wi)


def _down(gate, up, wo, g_of_tile, live_tiles, bm, bnd, interpret):
    r_pad, f = gate.shape
    d = wo.shape[-1]
    grid = (pl.cdiv(d, bnd), r_pad // bm)
    specs = [
        pl.BlockSpec((bm, f), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((bm, f), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, f, bnd), lambda j, i, g, lt: (g[i], 0, j)),
    ]
    out_specs = pl.BlockSpec((bm, bnd), lambda j, i, g, lt: (i, j))
    shape = jax.ShapeDtypeStruct((r_pad, d), gate.dtype)
    return _grid_call(_down_kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, gate, up, wo)


def _down_w(gate, up, w2, wo, g_of_tile, live_tiles, bm, bnd, interpret):
    r_pad, f = gate.shape
    d = wo.shape[-1]
    grid = (pl.cdiv(d, bnd), r_pad // bm)
    specs = [
        pl.BlockSpec((bm, f), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((bm, f), lambda j, i, g, lt: (i, 0)),
        # [nm, 1, bm] lanes-major: the TPU lowering requires the last
        # two block dims be (unit-or-full, 128-multiple)
        pl.BlockSpec((1, 1, bm), lambda j, i, g, lt: (i, 0, 0)),
        pl.BlockSpec((1, f, bnd), lambda j, i, g, lt: (g[i], 0, j)),
    ]
    out_specs = pl.BlockSpec((bm, bnd), lambda j, i, g, lt: (i, j))
    shape = jax.ShapeDtypeStruct((r_pad, d), gate.dtype)
    return _grid_call(_down_w_kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, gate, up, w2, wo)


def _dgdu_rc(dz, w2, xs, wg, wi, wo, g_of_tile, live_tiles, num_experts,
             bm, interpret):
    """→ (dg, du [R_pad, f], dwo [E, f, d] f32, dwp [n_f, nm, 1, bm]).
    f-tile size: DSTPU_GMM_BNF_BWD (default 256 — dz AND xs re-stream
    once per f-tile here, so bigger tiles cut the dominant HBM term;
    512 is the VMEM ceiling with the dwo accumulator resident)."""
    r_pad, d = dz.shape
    f = wg.shape[-1]
    # clamp at 512 regardless of the env: wg+wi+wo blocks plus the
    # (bnf, d) f32 dwo accumulator exceed scoped VMEM past it
    # (measured: 16.98M vs the 16M limit at bnf=512 on the 1B/8e bench)
    bnf = min(_block(f, int(os.environ.get("DSTPU_GMM_BNF_BWD", 256))),
              512)
    nf = pl.cdiv(f, bnf)
    nm = r_pad // bm
    grid = (nf, nm)
    specs = [
        pl.BlockSpec((bm, d), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, 1, bm), lambda j, i, g, lt: (i, 0, 0)),
        pl.BlockSpec((bm, d), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, d, bnf), lambda j, i, g, lt: (g[i], 0, j)),
        pl.BlockSpec((1, d, bnf), lambda j, i, g, lt: (g[i], 0, j)),
        pl.BlockSpec((1, bnf, d), lambda j, i, g, lt: (g[i], j, 0)),
    ]
    out_specs = [
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((1, bnf, d), lambda j, i, g, lt: (g[i], j, 0)),
        pl.BlockSpec((1, 1, 1, bm), lambda j, i, g, lt: (j, i, 0, 0)),
    ]
    shape = [jax.ShapeDtypeStruct((r_pad, f), dz.dtype),
             jax.ShapeDtypeStruct((r_pad, f), dz.dtype),
             jax.ShapeDtypeStruct((num_experts, f, d), jnp.float32),
             jax.ShapeDtypeStruct((nf, nm, 1, bm), jnp.float32)]
    scratch = [pltpu.VMEM((bnf, d), jnp.float32)]
    kernel = functools.partial(_dgdu_rc_kernel, f_total=f, bnf=bnf)
    return _grid_call(kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, dz, w2, xs, wg,
                      wi, wo, scratch=scratch)


def _dgdu(dy, wo, gate, up, g_of_tile, live_tiles, num_experts, bm,
          bnf, interpret):
    """→ (dg, du [R_pad, f], dwo [E, f, d] f32). Takes wo in its native
    [E, f, d] layout (f-slice blocks). The dwo accumulator block
    (1, bnf, d) f32 shares the step, so bnf is capped at 512 here to
    hold the VMEM budget."""
    r_pad, d = dy.shape
    f = gate.shape[-1]
    bnf = min(bnf, 512)
    grid = (pl.cdiv(f, bnf), r_pad // bm)
    specs = [
        pl.BlockSpec((bm, d), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, bnf, d), lambda j, i, g, lt: (g[i], j, 0)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
    ]
    out_specs = [
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((bm, bnf), lambda j, i, g, lt: (i, j)),
        pl.BlockSpec((1, bnf, d), lambda j, i, g, lt: (g[i], j, 0)),
    ]
    shape = [jax.ShapeDtypeStruct((r_pad, f), gate.dtype),
             jax.ShapeDtypeStruct((r_pad, f), gate.dtype),
             jax.ShapeDtypeStruct((num_experts, f, d), jnp.float32)]
    scratch = [pltpu.VMEM((bnf, d), jnp.float32)]
    return _grid_call(_dgdu_kernel, grid, specs, out_specs, shape,
                      interpret, g_of_tile, live_tiles, dy, wo, gate, up,
                      scratch=scratch)


def _dxs(dg, du, wg, wi, g_of_tile, live_tiles, bm, bnd, interpret):
    """dxs = dg·wg^T + du·wi^T with the weights in their native [E, d, f]
    layout (d-slice blocks, contraction on f).

    dg/du stream ONCE PER d-TILE here — the kernel's dominant HBM term
    (full-f rows: n_d × 2×[R,f]). So instead of halving the d-tile to
    fit the two full-K weight blocks in VMEM (4 d-tiles → 1.57 GB of
    dg/du traffic at the 16K-token bench), SUBDIVIDE the m-tiles to
    bm_x = 128: the aligned layout's tile boundaries are multiples of
    bm, so every 128-sub-tile still has one owning expert
    (``repeat(group_of_tile, bm/128)``) and d-tiles stay big.
    DSTPU_GMM_BND_BWD overrides the d-tile (default 512 → 2 sweeps)."""
    r_pad, f = dg.shape
    d = wg.shape[1]
    bnd_env = int(os.environ.get("DSTPU_GMM_BND_BWD", 0))
    if bm > 128 and bm % 128 == 0:
        bm_x = 128
        sub = bm // bm_x
        g_x = jnp.repeat(g_of_tile, sub)
        lt_x = live_tiles * sub
        bnd = _block(d, bnd_env or 512)
    else:
        # bm not 128-divisible: sub-tiles would straddle expert
        # boundaries — keep whole m-tiles and halve the d-tile for VMEM
        # (the pre-subdivision behavior)
        bm_x, g_x, lt_x = bm, g_of_tile, live_tiles
        bnd = max(_LANE, bnd // 2)
        bnd_env = 0          # the override only governs the 128-sub path
    # per-step footprint, double-buffered: dg + du rows (bm_x, f), two
    # full-f weight d-slices (bnd, f), one out block (bm_x, bnd). The
    # 2·bnd·f weight term scales with f, so long-ffn geometries must
    # clamp bnd the same way pick_blocks clamps bnf
    itemsize = dg.dtype.itemsize
    step = lambda: (2 * bm_x * f + 2 * bnd * f + bm_x * bnd) * itemsize * 2
    if bnd_env:
        if step() > _VMEM_BUDGET:
            raise ValueError(
                f"DSTPU_GMM_BND_BWD={bnd_env} needs {step()} bytes of "
                f"VMEM for the dxs tiles at f={f} (> {_VMEM_BUDGET} "
                f"budget); lower the override")
    else:
        while bnd > _LANE and step() > _VMEM_BUDGET:
            bnd //= 2
    grid = (pl.cdiv(d, bnd), r_pad // bm_x)
    specs = [
        pl.BlockSpec((bm_x, f), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((bm_x, f), lambda j, i, g, lt: (i, 0)),
        pl.BlockSpec((1, bnd, f), lambda j, i, g, lt: (g[i], j, 0)),
        pl.BlockSpec((1, bnd, f), lambda j, i, g, lt: (g[i], j, 0)),
    ]
    out_specs = pl.BlockSpec((bm_x, bnd), lambda j, i, g, lt: (i, j))
    shape = jax.ShapeDtypeStruct((r_pad, d), dg.dtype)
    return _grid_call(_dxs_kernel, grid, specs, out_specs, shape,
                      interpret, g_x, lt_x, dg, du, wg, wi)


# ---------------------------------------------------------------------------
# the differentiable FFN
# ---------------------------------------------------------------------------

def _dw_ragged(lhs, grad, sizes_padded, num_experts):
    """Weight gradient dW[e] = lhs[rows_e]^T @ grad[rows_e] via
    ragged_dot_general with the ragged dimension on the contraction —
    exact over the aligned layout because padding rows are zero in both
    operands.

    DSTPU_GMM_DW=zero is a BENCH-ONLY diagnostic that skips the weight
    gradients entirely (wrong training math) to expose their cost.
    """
    if os.environ.get("DSTPU_GMM_DW") == "zero":
        return jnp.zeros((num_experts, lhs.shape[1], grad.shape[1]),
                         lhs.dtype)
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return lax.ragged_dot_general(
        lhs, grad, sizes_padded, dims,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


@functools.lru_cache(maxsize=None)
def _build_ffn(bm: int, bnf: int, bnd: int, interpret: bool):
    """custom_vjp'd (xs, wg, wi, wo, group_of_tile, sizes_padded,
    live_tiles) -> Y. Rows at/past ``live_tiles * bm`` are UNSPECIFIED
    in every produced array (the kernels skip those tiles outright) —
    consumers must address rows through the dispatch maps only."""

    @jax.custom_vjp
    def ffn(xs, wg, wi, wo, g_of_tile, sizes_padded, live_tiles):
        gate, up = _gate_up(xs, wg, wi, g_of_tile, live_tiles, bm, bnf,
                            interpret)
        return _down(gate, up, wo, g_of_tile, live_tiles, bm, bnd,
                     interpret)

    def fwd(xs, wg, wi, wo, g_of_tile, sizes_padded, live_tiles):
        from jax.ad_checkpoint import checkpoint_name
        gate, up = _gate_up(xs, wg, wi, g_of_tile, live_tiles, bm, bnf,
                            interpret)
        # named so remat policies can SAVE the GLU pre-activations:
        # without them the layer backward re-runs the gate/up/down
        # kernels (3 of the FFN's 12 executed matmul units) just to
        # rebuild these residuals. ~2x[R, ffn] bf16 per layer — a
        # policy opt-in, not a default
        gate = checkpoint_name(gate, "moe_glu")
        up = checkpoint_name(up, "moe_glu")
        y = _down(gate, up, wo, g_of_tile, live_tiles, bm, bnd, interpret)
        return y, (xs, gate, up, wg, wi, wo, g_of_tile, sizes_padded,
                   live_tiles)

    def bwd(res, dy):
        (xs, gate, up, wg, wi, wo, g_of_tile, sizes_padded,
         live_tiles) = res
        e = wg.shape[0]
        dg, du, dwo32 = _dgdu(dy, wo, gate, up, g_of_tile, live_tiles,
                              e, bm, bnf, interpret)
        dxs = _dxs(dg, du, wg, wi, g_of_tile, live_tiles, bm, bnd,
                   interpret)
        dw_mode = os.environ.get("DSTPU_GMM_DW", "pallas")
        if dw_mode == "pallas":
            dwg, dwi = _dw_pair(xs, dg, du, g_of_tile, live_tiles, e,
                                bm, interpret)
            dwg = dwg.astype(wg.dtype)
            dwi = dwi.astype(wi.dtype)
            dwo = dwo32.astype(wo.dtype)
        else:   # 'ragged' (XLA fallback) / 'zero' (bench diagnostic)
            # the skipped dead-tail tiles leave dg/du/gate/up
            # UNINITIALIZED there, and sizes_padded[E-1] absorbs that
            # tail — zero it before the ragged reduction or 0*NaN
            # poisons the last expert's weight grads
            row = jnp.arange(xs.shape[0], dtype=jnp.int32)[:, None]
            alive = row < live_tiles[0] * bm
            dg_z = jnp.where(alive, dg, 0)
            du_z = jnp.where(alive, du, 0)
            dwg = _dw_ragged(xs, dg_z, sizes_padded, e)
            dwi = _dw_ragged(xs, du_z, sizes_padded, e)
            hidden = jnp.where(
                alive,
                (jax.nn.silu(gate.astype(jnp.float32))
                 * up.astype(jnp.float32)).astype(gate.dtype), 0)
            dwo = _dw_ragged(hidden, dy, sizes_padded, e)
        return (dxs, dwg, dwi, dwo,
                np.zeros(g_of_tile.shape, jax.dtypes.float0),
                np.zeros(sizes_padded.shape, jax.dtypes.float0),
                np.zeros(live_tiles.shape, jax.dtypes.float0))

    ffn.defvjp(fwd, bwd)
    return ffn


@functools.lru_cache(maxsize=None)
def _build_ffn_w(bm: int, bnf: int, bnd: int, interpret: bool):
    """Scaled variant: (xs, w2, wg, wi, wo, meta…) -> Z with the per-row
    combine weights applied in the down kernel and their gradient
    computed in the dgdu kernel (see :func:`_dgdu_rc_kernel`). The VJP
    residuals are just (xs, w2, weights, dispatch metadata) — no [R,f]
    tensors: the backward recomputes gate/up in-kernel, so under ANY
    remat policy the layer backward re-runs zero kernels (no ``moe_glu``
    save needed; that name only matters for the unscaled path)."""

    @jax.custom_vjp
    def ffn(xs, w2, wg, wi, wo, g_of_tile, sizes_padded, live_tiles):
        gate, up = _gate_up(xs, wg, wi, g_of_tile, live_tiles, bm, bnf,
                            interpret)
        return _down_w(gate, up, w2, wo, g_of_tile, live_tiles, bm, bnd,
                       interpret)

    def fwd(xs, w2, wg, wi, wo, g_of_tile, sizes_padded, live_tiles):
        gate, up = _gate_up(xs, wg, wi, g_of_tile, live_tiles, bm, bnf,
                            interpret)
        z = _down_w(gate, up, w2, wo, g_of_tile, live_tiles, bm, bnd,
                    interpret)
        # residuals carry NO [R, f] tensors: the backward recomputes
        # gate/up in-kernel from xs (_dgdu_rc_kernel), so under any
        # remat policy the layer backward re-runs nothing and the GLU
        # pre-activations never round-trip HBM in the backward
        return z, (xs, w2, wg, wi, wo, g_of_tile, sizes_padded,
                   live_tiles)

    def bwd(res, dz):
        (xs, w2, wg, wi, wo, g_of_tile, sizes_padded,
         live_tiles) = res
        e = wg.shape[0]
        dg, du, dwo32, dwp = _dgdu_rc(dz, w2, xs, wg, wi, wo, g_of_tile,
                                      live_tiles, e, bm, interpret)
        if os.environ.get("DSTPU_GMM_DCOMBINE") == "zero":
            # BENCH-ONLY diagnostic: drop the router's training signal
            # to expose the combine-weight-grad cost
            dw2 = jnp.zeros_like(w2)
        else:
            # dwp m-tiles at/past live_tiles are SKIPPED by the kernel
            # (uninitialized memory) — mask them before handing the
            # combine-weight grad to the optimizer, or garbage/NaNs in
            # the dead tail poison the router update
            tile = jnp.arange(dwp.shape[1], dtype=jnp.int32)[:, None, None]
            dw2 = jnp.where(tile < live_tiles[0],
                            jnp.sum(dwp, axis=0), 0.0
                            ).astype(w2.dtype)            # [nm, 1, bm]
        dxs = _dxs(dg, du, wg, wi, g_of_tile, live_tiles, bm, bnd,
                   interpret)
        dw_mode = os.environ.get("DSTPU_GMM_DW", "pallas")
        if dw_mode == "pallas":
            dwg, dwi = _dw_pair(xs, dg, du, g_of_tile, live_tiles, e,
                                bm, interpret)
            dwg = dwg.astype(wg.dtype)
            dwi = dwi.astype(wi.dtype)
            dwo = dwo32.astype(wo.dtype)
        else:   # 'ragged' (XLA fallback) / 'zero' (bench diagnostic)
            row = jnp.arange(xs.shape[0], dtype=jnp.int32)[:, None]
            alive = row < live_tiles[0] * bm
            dg_z = jnp.where(alive, dg, 0)
            du_z = jnp.where(alive, du, 0)
            dwg = _dw_ragged(xs, dg_z, sizes_padded, e)
            dwi = _dw_ragged(xs, du_z, sizes_padded, e)
            # gate/up are no longer residuals — rebuild hidden over the
            # aligned layout (exact: padding rows are zero in xs)
            gate_r = lax.ragged_dot(xs, wg, sizes_padded)
            up_r = lax.ragged_dot(xs, wi, sizes_padded)
            hidden = jnp.where(
                alive,
                (jax.nn.silu(gate_r.astype(jnp.float32))
                 * up_r.astype(jnp.float32)).astype(gate_r.dtype), 0)
            # d(h·wo) = w ⊙ dZ under the fused scaling
            dzw = jnp.where(
                alive,
                dz * w2.reshape(-1, 1).astype(dz.dtype), 0)
            dwo = _dw_ragged(hidden, dzw, sizes_padded, e)
        return (dxs, dw2, dwg, dwi, dwo,
                np.zeros(g_of_tile.shape, jax.dtypes.float0),
                np.zeros(sizes_padded.shape, jax.dtypes.float0),
                np.zeros(live_tiles.shape, jax.dtypes.float0))

    ffn.defvjp(fwd, bwd)
    return ffn


@jax.custom_vjp
def gather_sum(z: jax.Array, sorted_tok: jax.Array,
               pos: jax.Array) -> jax.Array:
    """out[t] = Σ_slot z[pos[slot,t]] — the UNWEIGHTED combine gather for
    the scaled FFN (combine weights applied in-kernel; pos [k, S]).
    Residual-free: the VJP is the opposite gather, so nothing of the FFN
    output has to survive to (or be rebuilt for) the backward pass."""
    out = z[pos[0]]
    for slot in range(1, pos.shape[0]):
        out = out + z[pos[slot]]
    return out


def _gather_sum_fwd(z, sorted_tok, pos):
    return gather_sum(z, sorted_tok, pos), (sorted_tok, pos.shape)


def _gather_sum_bwd(res, dout):
    sorted_tok, pos_shape = res
    # sentinel rows (padding / dead tail) index the appended zero row
    dout1 = jnp.concatenate(
        [dout, jnp.zeros((1, dout.shape[-1]), dout.dtype)])
    return (dout1[sorted_tok], np.zeros(sorted_tok.shape,
                                        jax.dtypes.float0),
            np.zeros(pos_shape, jax.dtypes.float0))


gather_sum.defvjp(_gather_sum_fwd, _gather_sum_bwd)


def grouped_glu_ffn(xs: jax.Array, wg: jax.Array, wi: jax.Array,
                    wo: jax.Array, group_of_tile: jax.Array,
                    sizes_padded: jax.Array, live_tiles: jax.Array, *,
                    bm: int, bnf: int, bnd: int,
                    w: Optional[jax.Array] = None,
                    interpret: bool = False) -> jax.Array:
    """Grouped SwiGLU FFN over a block-aligned sorted row layout.

    xs [R_pad, d] (rows sorted by expert, padding rows zero), wg/wi
    [E, d, f], wo [E, f, d] → Y [R_pad, d].

    ``w=None``: unscaled output; the caller applies combine weights
    (gate-weight gradient stays in autodiff-land via
    :func:`gather_combine`). ``w`` [R_pad] (``sorted_w`` from
    :func:`aligned_dispatch`): the weights are fused into the down
    kernel, their gradient into the dgdu kernel, and the output is
    combined with the residual-free :func:`gather_sum` — the fast
    training path.
    """
    if w is None:
        return _build_ffn(bm, bnf, bnd, interpret)(
            xs, wg, wi, wo, group_of_tile, sizes_padded, live_tiles)
    if bm % _LANE:
        raise ValueError(
            f"grouped_glu_ffn(w=...): the fused-combine path's "
            f"lanes-major w tiles require bm % {_LANE} == 0, got bm={bm}"
            f"; pass w=None and apply combine weights via gather_combine")
    w2 = w.reshape(xs.shape[0] // bm, 1, bm)
    return _build_ffn_w(bm, bnf, bnd, interpret)(
        xs, w2, wg, wi, wo, group_of_tile, sizes_padded, live_tiles)
