"""Flash attention — Pallas TPU kernel.

The TPU-native replacement for the reference's fused attention kernels
(csrc/transformer/inference/csrc/softmax.cu + the blocked_flash bindings
under deepspeed/inference/v2/kernels/ragged_ops/). Blockwise online-softmax
attention: the [T, T] score matrix is never materialized in HBM — each
(query-block, kv-block) tile lives only in VMEM — so backward needs no
saved probabilities, just the per-row logsumexp (the same residual layout
flash-attention-2 uses).

Layout: heads are folded into the grid's leading axis ([B*H, T, D]); GQA
maps query-head index -> kv-head index inside the BlockSpec index maps, so
K/V are never repeated in memory. fp32 accumulation on the MXU
(preferred_element_type), bf16 inputs.

Two kernel generations, auto-dispatched on local sequence length:
- resident (tk*d*itemsize ≤ 2 MiB, i.e. up to 8K at d=128 bf16): whole
  K/V per program, causal fori_loop bound skips dead blocks and their
  fetches — fastest.
- XL: (bh, nq, nk) grid with kv innermost, online-softmax state in VMEM
  scratch — no sequence ceiling (128K+ local seq; the Ulysses-128K
  config needs 16K+ per chip at SP=8).

Falls back to the XLA reference implementation (models.transformer.
dot_product_attention) off-TPU or for shapes the kernel doesn't cover.
"""

import functools
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _mask_scores(s, q_start, k_start, causal: bool,
                 window, transposed: bool = False) -> "jax.Array":
    """Apply the causal and/or sliding-window visibility mask to one
    [BQ, BK] score tile — ``transposed``: [BK, BQ] — (the ONE home for the
    mask inequalities — used by every fwd/bwd kernel generation)."""
    if not causal and window is None:
        return s
    qpos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, int(transposed))
    kpos = k_start + lax.broadcasted_iota(jnp.int32, s.shape,
                                          int(not transposed))
    ok = (qpos >= kpos) if causal else \
        jnp.full_like(qpos, True, dtype=jnp.bool_)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    return jnp.where(ok, s, _NEG_INF)


def _div(a, b):
    """``a`` over a positive ``b`` for host ints and traced int32 scalars.
    The two round a negative quotient differently (floor / toward zero);
    every caller clamps it at 0, where they agree."""
    return a // b if isinstance(a, int) else lax.div(a, jnp.int32(b))


def _clamp(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _tile_ranges(axis: str, start, block_q: int, block_k: int, n: int,
                 causal: bool, window, q_offset=0):
    """The ONE home for a score tile's CLASS. For one q block (``axis``
    'k': its queries are ``[start, start + block_q)``, ``q_offset``
    included) the k blocks it walks, or for one k block (``axis`` 'q':
    keys ``[start, start + block_k)``) the q blocks that walk it, out of
    ``n``: ``(lo, a, b, hi)`` with

    - ``[a, b)`` INTERIOR: the tile's last key is at or before its first
      query (``k_start + block_k - 1 <= q_start``, when causal) and its
      first key is inside its LAST query's window (``k_start > q_start +
      block_q - 1 - window``, when windowed) — every pair is visible, so
      `_mask_scores` would be the identity on it;
    - ``[lo, a)`` and ``[b, hi)`` EDGE: live, and some pair is masked (on
      the 'k' axis the window's side, then the diagonal; on the 'q' axis
      the diagonal, then the window's side);
    - the rest SKIPPED: no pair is visible (no query reaches the tile's
      first key, or its last key left every query's window).

    ``start`` is a host int (`tile_classes`) or a traced int32 scalar (the
    kernels): the arithmetic is the same."""
    bq, bk = block_q, block_k
    if axis == "k":
        q_start, q_last = start, start + bq - 1
        # live: k_start <= q_last  and  k_start + bk - 1 > q_start - window
        lo = 0 if window is None else \
            _clamp(_div(q_start - window + 1, bk), 0, n)
        hi = _clamp(_div(q_last + bk, bk), 0, n) if causal else n
        # interior: k_start + bk - 1 <= q_start  and  k_start > q_last - window
        a = lo if window is None else _div(q_last - window + bk, bk)
        b = _div(q_start + 1, bk) if causal else hi
    else:
        k_start, k_last = start, start + bk - 1
        # live: q_start + bq - 1 >= k_start  and  q_start - window < k_last
        lo = _clamp(_div(k_start - q_offset, bq), 0, n) if causal else 0
        hi = n if window is None else \
            _clamp(_div(k_last + window - 1 - q_offset + bq, bq), 0, n)
        # interior: q_start >= k_last  and  q_start + bq - 1 - window < k_start
        a = _div(k_last - q_offset + bq - 1, bq) if causal else lo
        b = hi if window is None else _div(k_start + window - q_offset, bq)
    a = _clamp(a, lo, hi)
    return lo, a, _clamp(b, a, hi), hi


def _block_starts(axis, tq, tk, block_q, block_k, q_offset):
    """(start of each block that walks ``axis``, number of blocks on it)."""
    if axis == "k":
        return range(q_offset, q_offset + tq, block_q), tk // block_k
    return range(0, tk, block_k), tq // block_q


#: a range EVERY block of a call walks exactly this many times (a causal
#: call's diagonal tile at block_q == block_k; an own-chunk call's one
#: tile) is emitted as straight-line code, not as a loop
_STRAIGHT_LINE_TILES = 1


def _class_trips(axis, tq, tk, block_q, block_k, causal, window, q_offset):
    """What a kernel emits for each of a block's three ranges (edge ``[lo,
    a)``, interior ``[a, b)``, edge ``[b, hi)``): 0 = nothing (no block of
    the call enters it), 1 = the tile body once, straight-line (EVERY block
    holds exactly one tile there), None = a loop (the count varies by
    block). Tile counts are static, so a class that cannot occur costs no
    code: a call of one q block and one k block (a split step's own-chunk
    attention) is its one masked tile."""
    starts, n = _block_starts(axis, tq, tk, block_q, block_k, q_offset)
    rs = [_tile_ranges(axis, s, block_q, block_k, n, causal, window,
                       q_offset) for s in starts]
    def emitted(i):
        counts = {r[i + 1] - r[i] for r in rs}
        return min(counts) if len(counts) == 1 and \
            min(counts) <= _STRAIGHT_LINE_TILES else None
    return tuple(emitted(i) for i in range(3))


def tile_classes(tq: int, tk: int, block_q: int, block_k: int, causal: bool,
                 window=None, q_offset: int = 0) -> Tuple[int, int, int]:
    """(interior, edge, skipped) score tiles of one head of a call — the
    kernels' own classification (`_tile_ranges`) on host ints."""
    starts, n = _block_starts("k", tq, tk, block_q, block_k, q_offset)
    interior = edge = 0
    for s in starts:
        lo, a, b, hi = _tile_ranges("k", s, block_q, block_k, n, causal,
                                    window)
        interior += b - a
        edge += (a - lo) + (hi - b)
    return interior, edge, len(starts) * n - interior - edge


def _count_tiles(tq, tk, block_q, block_k, causal, window, q_offset):
    """Bump ``flash/tiles_*`` by one head's tiles of one kernel call — at
    trace time, so once a program build and not a step (lazy import:
    telemetry pulls in the whole diagnostics stack)."""
    from deepspeed_tpu.telemetry.registry import registry
    for name, n in zip(("interior", "edge", "skipped"), tile_classes(
            tq, tk, block_q, block_k, causal, window, q_offset)):
        registry.counter("flash/tiles_" + name).inc(n)


def _class_fori(ranges, trips, tile, carry):
    """Run ``tile(masked)``'s loop body over a block's three ranges in
    order, the carry shared: masked over the edge ranges, unmasked over
    the interior one; ``trips`` (`_class_trips`) says what each range is
    emitted as. On the chip a second LOOP costs a q block more than the
    mask it saves (0.19 us at 512 x 512: its carry of 192 registers is
    handed over in VMEM and nothing is scheduled across its boundary),
    which is why the one tile every block holds is straight-line code."""
    lo, a, b, hi = ranges
    for (start, stop), n, masked in zip(((lo, a), (a, b), (b, hi)), trips,
                                        (True, False, True)):
        if n is None:
            carry = lax.fori_loop(start, stop, tile(masked), carry)
        else:
            for i in range(n):
                carry = tile(masked)(start + i, carry)
    return carry


def _class_when(j, ranges, trips, tile):
    """`_class_fori` for the XL grids, whose tiles are grid steps: step
    ``j`` of a block's row runs ``tile(masked)`` under its class's
    ``pl.when``, and a skipped step runs nothing."""
    lo, a, b, hi = ranges
    live = jnp.logical_and(j >= lo, j < hi)
    if trips[1] == 0:
        pl.when(live)(tile(True))
        return
    interior = jnp.logical_and(j >= a, j < b)
    pl.when(interior)(tile(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(tile(True))


# swept on v5e (1.27B llama, seq 2048): 512/512 → 51.3% MFU vs 47.9% at
# 256/256 and 50.9% at 1024/512 — bigger q tiles amortize the softmax
# bookkeeping until VMEM pressure bites
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale: float, causal: bool, block_k: int, q_offset: int,
                window: Optional[int], trips):
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    seq_k = k_ref.shape[1]
    d = q_ref.shape[2]

    # operands stay bf16 — the MXU accumulates in fp32 via
    # preferred_element_type; an eager .astype(f32) would force 8x-slower
    # fp32 matmuls (measured 12 vs 90+ TF/s on v5e)
    q = q_ref[0]                                           # [BQ, D]
    q_start = qi * block_q + q_offset
    # only k blocks that intersect the causal triangle; blocks left of the
    # sliding window (Mistral SWA: key kp visible to query qp iff
    # qp - window < kp <= qp) are SKIPPED, so FLOPs scale with window,
    # not T²; of the live ones only the EDGE tiles are masked
    ranges = _tile_ranges("k", q_start, block_q, block_k, seq_k // block_k,
                          causal, window)

    def tile(masked):
        def body(kb, carry):
            acc, m, l = carry
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, q_start, kb * block_k, causal, window)
            blk_max = jnp.max(s, axis=1)                    # [BQ]
            new_m = jnp.maximum(m, blk_max)
            p = jnp.exp(s - new_m[:, None])
            corr = jnp.exp(m - new_m)
            if masked:
                # rows with no live key yet: new_m == -inf -> p must be 0
                # (an interior tile's rows see every key: both selects
                # are the identity there)
                alive = new_m > _NEG_INF / 2
                p = jnp.where(alive[:, None], p, 0.0)
                corr = jnp.where(alive, corr, 0.0)
            acc = acc * corr[:, None] + lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l = l * corr + jnp.sum(p, axis=1)
            return acc, new_m, l
        return body

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = _class_fori(ranges, trips, tile, (acc0, m0, l0))

    safe_l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)
    # lse layout [BH, 1, TQ]: full row resident per bh, each qi program
    # writes its slice (satisfies the (8,128) tile rule via dim equality)
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = jnp.where(
        m > _NEG_INF / 2, m + jnp.log(safe_l), _NEG_INF)


def _fwd(q, k, v, scale, causal, q_offset, block_q, block_k, window,
         interpret):
    if not _resident_ok(q.shape[1], k.shape[1], q.shape[2],
                        q.dtype.itemsize):
        return _fwd_xl(q, k, v, scale, causal, q_offset, block_q, block_k,
                       window, interpret)
    bh, tq, d = q.shape
    bkv, tk, _ = k.shape
    g = bh // bkv
    grid = (bh, tq // block_q)
    geometry = (tq, tk, block_q, block_k, causal, window, q_offset)
    _count_tiles(*geometry)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, q_offset=q_offset,
                          window=window,
                          trips=_class_trips("k", *geometry)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, g=g: (lax.div(b, g), 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, g=g: (lax.div(b, g), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# XL forward kernel — KV-blocked grid for long sequences.
#
# The resident kernel above keeps whole K/V per program (fastest at tk
# ≤ ~8K: the causal fori_loop bound skips dead blocks AND their fetches).
# Past that the (1, tk, d) BlockSpec overflows VMEM, so this variant runs
# a (bh, nq, nk) grid with the kv dimension innermost and carries the
# online-softmax state (acc, m, l) in VMEM scratch across kv steps —
# the standard FA2 TPU structure (compare jax.experimental.pallas.ops.
# tpu.flash_attention; re-derived here). Causally-dead (i, j) programs
# skip compute via pl.when, and their K/V index maps are CLAMPED onto
# the nearest live block: Pallas only issues a copy when an operand's
# mapped block index changes between consecutive grid steps, so the
# dead tail (causal) / dead head (sliding window) of each kv row costs
# no DMA either — ~2x less attention HBM traffic at long causal seqs.
# ---------------------------------------------------------------------------


def _xl_kv_index(g, block_q, block_k, q_offset, causal, window, num_kb):
    """K/V BlockSpec index map for the (b, i, j) XL grids (kv innermost).

    Dead (i, j) steps map onto the nearest live kv block, so consecutive
    dead steps re-reference an already-resident block and their copies
    are elided. The clamp is allowed to be conservative (at worst one
    extra block fetched); compute is independently gated by ``pl.when``
    in the kernel, so correctness never depends on it."""
    def idx(b, i, j):
        jj = j
        if causal:
            # last live block: j*bk <= q_start + bq - 1
            jmax = lax.div(i * block_q + q_offset + block_q - 1, block_k)
            jj = lax.min(jj, jmax)
        if window is not None:
            # first live block: j*bk + bk - 1 > q_start - window
            jmin = lax.max(
                0, lax.div(i * block_q + q_offset - window + 1, block_k))
            jj = lax.max(jj, lax.min(jmin, num_kb - 1))
        return (lax.div(b, g), jj, 0)
    return idx


def _xl_q_index(block_q, block_k, q_offset, causal, window, num_qb,
                lse_like: bool = False):
    """Q-side BlockSpec index map for the (b, jk, iq) dkv grid (q
    innermost): clamp dead head (causal) / dead tail (window) steps of
    each q row onto the nearest live q block (same DMA-elision argument
    as `_xl_kv_index`)."""
    def idx(b, jk, iq):
        ii = iq
        if causal:
            # first live q block: iq*bq + q_offset + bq - 1 >= jk*bk
            imin = lax.max(0, lax.div(jk * block_k - q_offset, block_q))
            ii = lax.max(ii, lax.min(imin, num_qb - 1))
        if window is not None:
            # last live q block: iq*bq + q_offset - window < jk*bk + bk - 1
            imax = lax.div(jk * block_k + block_k - 2 + window - q_offset,
                           block_q)
            ii = lax.min(ii, lax.max(imax, 0))
        return (b, 0, ii) if lse_like else (b, ii, 0)
    return idx

def _fwd_kernel_xl(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, causal: bool, q_offset: int,
                   window: Optional[int], num_kb: int, trips):
    i = pl.program_id(1)
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q_start = i * block_q + q_offset
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked):
        def _compute():
            q = q_ref[0]
            k_blk = k_ref[0]
            v_blk = v_ref[0]
            s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, q_start, k_start, causal, window)
            m = m_ref[...]
            blk_max = jnp.max(s, axis=1)
            new_m = jnp.maximum(m, blk_max)
            new_m_col = new_m[:, None]
            p = jnp.exp(s - new_m_col)
            corr = jnp.exp(m - new_m)
            if masked:
                # Mosaic can't minor-dim-reshape i1 vectors — compare the
                # already 2-D f32 column instead of reshaping a 1-D bool
                p = jnp.where(new_m_col > _NEG_INF / 2, p, 0.0)
                corr = jnp.where(new_m > _NEG_INF / 2, corr, 0.0)
            acc_ref[...] = acc_ref[...] * corr[:, None] + lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
            m_ref[...] = new_m
        return _compute

    _class_when(j, _tile_ranges("k", q_start, block_q, block_k, num_kb,
                                causal, window), trips, tile)

    @pl.when(j == num_kb - 1)
    def _flush():
        l = l_ref[...]
        m = m_ref[...]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :] = jnp.where(
            m > _NEG_INF / 2, m + jnp.log(safe_l), _NEG_INF)


def _fwd_xl(q, k, v, scale, causal, q_offset, block_q, block_k, window,
            interpret):
    bh, tq, d = q.shape
    bkv, tk, _ = k.shape
    g = bh // bkv
    num_kb = tk // block_k
    grid = (bh, tq // block_q, num_kb)
    kv_idx = _xl_kv_index(g, block_q, block_k, q_offset, causal, window,
                          num_kb)
    geometry = (tq, tk, block_q, block_k, causal, window, q_offset)
    _count_tiles(*geometry)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_xl, scale=scale, causal=causal,
                          q_offset=q_offset, window=window, num_kb=num_kb,
                          trips=_class_trips("k", *geometry)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_xl",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (flash-attention-2 style: recompute p from q,k + lse)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale: float, causal: bool, block_k: int,
                   q_offset: int, window: Optional[int], trips):
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    seq_k = k_ref.shape[1]
    d = q_ref.shape[2]

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
    q_start = qi * block_q + q_offset
    ranges = _tile_ranges("k", q_start, block_q, block_k, seq_k // block_k,
                          causal, window)

    def tile(masked):
        def body(kb, dq):
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, q_start, kb * block_k, causal, window)
            p = jnp.exp(s - lse[:, None])
            dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * scale).astype(k_blk.dtype)
            return dq + lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        return body

    dq = _class_fori(ranges, trips, tile,
                     jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale: float, causal: bool,
                    block_q: int, q_offset: int, window: Optional[int],
                    trips):
    ki = pl.program_id(1)
    block_k = k_ref.shape[1]
    seq_q = q_ref.shape[1]
    d = k_ref.shape[2]

    k_blk = k_ref[0]                                       # [BK, D]
    v_blk = v_ref[0]
    k_start = ki * block_k
    # from the first q block whose END reaches this k block's start to the
    # last one whose window still holds its last key
    ranges = _tile_ranges("q", k_start, block_q, block_k, seq_q // block_q,
                          causal, window, q_offset)

    def tile(masked):
        def body(qb, carry):
            # the tile TRANSPOSED, [BK, BQ]: s^T = k·q^T and dp^T = v·do^T
            # come off the MXU as the LHS the two accumulating matmuls
            # take, p^T·do and ds^T·q — computed as [BQ, BK] those two
            # contract over rows and Mosaic transposes p and ds on the
            # XLU, a tile each (0.29 us of the tile's 1.97 at 512 x 512)
            dk, dv = carry
            q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
            do = do_ref[0, pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[0, :, pl.ds(qb * block_q, block_q)]     # [1, BQ]
            delta = delta_ref[0, :, pl.ds(qb * block_q, block_q)]
            s = lax.dot_general(k_blk, q_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, qb * block_q + q_offset, k_start,
                                 causal, window, transposed=True)
            p = jnp.exp(s - lse)
            dv = dv + lax.dot_general(p.astype(do.dtype), do,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dp = lax.dot_general(v_blk, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q_blk.dtype)
            dk = dk + lax.dot_general(ds, q_blk, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            return dk, dv
        return body

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = _class_fori(ranges, trips, tile, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, scale, causal, q_offset, block_q, block_k,
         window, interpret):
    if not _resident_ok(q.shape[1], k.shape[1], q.shape[2],
                        q.dtype.itemsize):
        return _bwd_xl(q, k, v, out, lse, do, scale, causal, q_offset,
                       block_q, block_k, window, interpret)
    bh, tq, d = q.shape
    bkv, tk, _ = k.shape
    g = bh // bkv
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                      # [BH, 1, TQ]
    # the two kernels walk the same tiles, by rows and by columns
    geometry = (tq, tk, block_q, block_k, causal, window, q_offset)
    _count_tiles(*geometry)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, q_offset=q_offset,
                          window=window,
                          trips=_class_trips("k", *geometry)),
        grid=(bh, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, g=g: (lax.div(b, g), 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b, i, g=g: (lax.div(b, g), 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv per q-head, summed over the GQA group afterwards
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, q_offset=q_offset,
                          window=window,
                          trips=_class_trips("q", *geometry)),
        grid=(bh, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, g=g: (lax.div(b, g), i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, g=g: (lax.div(b, g), i, 0)),
            pl.BlockSpec((1, tq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    if g > 1:
        dk = dk_h.reshape(bkv, g, tk, d).sum(axis=1)
        dv = dv_h.reshape(bkv, g, tk, d).sum(axis=1)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# XL backward kernels — KV/Q-blocked grids mirroring _fwd_kernel_xl
# ---------------------------------------------------------------------------

def _bwd_dq_kernel_xl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc_ref, *, scale: float, causal: bool,
                      q_offset: int, window: Optional[int], num_kb: int,
                      trips):
    i = pl.program_id(1)
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q_start = i * block_q + q_offset
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def tile(masked):
        def _compute():
            q = q_ref[0]
            do = do_ref[0]
            lse = lse_ref[0, 0, :]
            delta = delta_ref[0, 0, :]
            k_blk = k_ref[0]
            v_blk = v_ref[0]
            s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, q_start, k_start, causal, window)
            p = jnp.exp(s - lse[:, None])
            dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * scale).astype(k_blk.dtype)
            dq_acc_ref[...] = dq_acc_ref[...] + lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return _compute

    _class_when(j, _tile_ranges("k", q_start, block_q, block_k, num_kb,
                                causal, window), trips, tile)

    @pl.when(j == num_kb - 1)
    def _flush():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_xl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                       scale: float, causal: bool, q_offset: int,
                       window: Optional[int], num_qb: int, trips):
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    k_start = jk * block_k
    q_start = iq * block_q + q_offset

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def tile(masked):
        def _compute():
            k_blk = k_ref[0]
            v_blk = v_ref[0]
            q_blk = q_ref[0]
            do = do_ref[0]
            lse = lse_ref[0, 0, :]
            delta = delta_ref[0, 0, :]
            s = lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _mask_scores(s, q_start, k_start, causal, window)
            p = jnp.exp(s - lse[:, None])
            dv_acc_ref[...] = dv_acc_ref[...] + lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None]) * scale).astype(q_blk.dtype)
            dk_acc_ref[...] = dk_acc_ref[...] + lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return _compute

    _class_when(iq, _tile_ranges("q", k_start, block_q, block_k, num_qb,
                                 causal, window, q_offset), trips, tile)

    @pl.when(iq == num_qb - 1)
    def _flush():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_xl(q, k, v, out, lse, do, scale, causal, q_offset, block_q,
            block_k, window, interpret):
    bh, tq, d = q.shape
    bkv, tk, _ = k.shape
    g = bh // bkv
    num_kb = tk // block_k
    num_qb = tq // block_q
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                      # [BH, 1, TQ]

    kv_idx = _xl_kv_index(g, block_q, block_k, q_offset, causal, window,
                          num_kb)
    geometry = (tq, tk, block_q, block_k, causal, window, q_offset)
    _count_tiles(*geometry)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_xl, scale=scale, causal=causal,
                          q_offset=q_offset, window=window, num_kb=num_kb,
                          trips=_class_trips("k", *geometry)),
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq_xl",
    )(q, k, v, do, lse, delta)

    q_idx = _xl_q_index(block_q, block_k, q_offset, causal, window, num_qb)
    lse_idx = _xl_q_index(block_q, block_k, q_offset, causal, window,
                          num_qb, lse_like=True)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_xl, scale=scale, causal=causal,
                          q_offset=q_offset, window=window, num_qb=num_qb,
                          trips=_class_trips("q", *geometry)),
        grid=(bh, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, block_k, d),
                         lambda b, jk, iq, g=g: (lax.div(b, g), jk, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, jk, iq, g=g: (lax.div(b, g), jk, 0)),
            pl.BlockSpec((1, block_q, d), q_idx),
            pl.BlockSpec((1, 1, block_q), lse_idx),
            pl.BlockSpec((1, 1, block_q), lse_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, jk, iq: (b, jk, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, jk, iq: (b, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv_xl",
    )(q, k, v, do, lse, delta)

    if g > 1:
        dk = dk_h.reshape(bkv, g, tk, d).sum(axis=1)
        dv = dv_h.reshape(bkv, g, tk, d).sum(axis=1)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, q_offset, block_q, block_k, window, interpret,
           bwd_block_q, bwd_block_k):
    out, _ = _fwd(q, k, v, 1.0 / math.sqrt(q.shape[-1]), causal, q_offset,
                  block_q, block_k, window, interpret)
    return out


def _flash_fwd(q, k, v, causal, q_offset, block_q, block_k, window,
               interpret, bwd_block_q, bwd_block_k):
    out, lse = _fwd(q, k, v, 1.0 / math.sqrt(q.shape[-1]), causal, q_offset,
                    block_q, block_k, window, interpret)
    # name the custom_vjp residuals so remat policies can SAVE them: with
    # plain 'save_attn_out' (post-projection value) the backward re-runs
    # this whole forward kernel just to rebuild (out, lse) — a full extra
    # attention pass per layer. 'save_attn_kernel' saves these two instead
    # (same bytes: out is B·T·d like the projected value; lse is ~1% more)
    # and the backward recomputes only the cheap wo projection.
    out = checkpoint_name(out, "attn_kernel_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, block_q, block_k, window, interpret,
               bwd_block_q, bwd_block_k, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, g,
                      1.0 / math.sqrt(q.shape[-1]), causal, q_offset,
                      bwd_block_q or block_q, bwd_block_k or block_k,
                      window, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


#: per-tensor VMEM budget for the full-K/V-resident BlockSpecs. A core has
#: ~16 MiB and Pallas DOUBLE-BUFFERS revisited blocks, so the dq kernel's
#: K+V residency costs ~4x this bound in stack VMEM (measured: 16.75 MiB
#: at tk=16K/d=128 under a 4 MiB bound → compile OOM). 2 MiB keeps the
#: fast resident kernels through tk=8K at d=128; beyond that the XL
#: (KV-blocked-grid) kernels take over — no sequence ceiling.
_VMEM_PER_TENSOR = 2 * 1024 * 1024


def _resident_ok(tq, tk, d, itemsize=2) -> bool:
    """Whole-K/V-per-program kernels fit VMEM (the fast path: the causal
    fori_loop bound skips dead blocks AND their fetches)."""
    return max(tq, tk) * d * itemsize <= _VMEM_PER_TENSOR


def _supported(tq, tk, d, block_q, block_k) -> bool:
    return (tq % block_q == 0 and tk % block_k == 0 and
            tq >= block_q and tk >= block_k and d <= 256)


def _pick_blocks(tq, tk, d, itemsize, block_q=None, block_k=None):
    """Block selection shared by every public wrapper: explicit args, the
    DSTPU_FLASH_BQ/BK env knobs, per-generation defaults (XL grids want
    1024/1024 — measured 44.8%% vs 36.0%% MFU at 512/512, seq 16K v5e),
    then step-down until the shape divides (e.g. tq=768 runs at 256 —
    far faster than the XLA fallback)."""
    import os
    xl = not _resident_ok(tq, tk, d, itemsize)
    default_bq = 1024 if xl else DEFAULT_BLOCK_Q
    default_bk = 1024 if xl else DEFAULT_BLOCK_K
    bq = block_q or int(os.environ.get("DSTPU_FLASH_BQ", 0)) or \
        min(default_bq, tq)
    bk = block_k or int(os.environ.get("DSTPU_FLASH_BK", 0)) or \
        min(default_bk, tk)
    bq, bk = min(bq, tq), min(bk, tk)
    while bq > 128 and (tq % bq or not _supported(tq, tk, d, bq, bk)):
        bq //= 2
    while bk > 128 and (tk % bk or not _supported(tq, tk, d, bq, bk)):
        bk //= 2
    return bq, bk


def _pick_bwd_blocks(tq, tk, d, itemsize, fwd_bq, fwd_bk):
    """Backward kernels carry more VMEM state (fp32 dq/dk/dv accumulators +
    the extra do/delta operands), so their sweet spot differs from the
    forward's — e.g. fwd 2048×1024 is the 16K winner but the dq kernel
    stack-OOMs past bq 1024. Defaults to the forward blocks; override via
    DSTPU_FLASH_BWD_BQ/BK."""
    import os
    bq = int(os.environ.get("DSTPU_FLASH_BWD_BQ", 0)) or fwd_bq
    bk = int(os.environ.get("DSTPU_FLASH_BWD_BK", 0)) or fwd_bk
    bq, bk = min(bq, tq), min(bk, tk)
    while bq > 128 and (tq % bq or not _supported(tq, tk, d, bq, bk)):
        bq //= 2
    while bk > 128 and (tk % bk or not _supported(tq, tk, d, bq, bk)):
        bk //= 2
    return bq, bk


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    q_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Drop-in ``attn_fn``: q [B,T,H,D], k/v [B,T,KvH,D] → [B,T,H,D].

    Uses the Pallas kernel on TPU (or interpret mode elsewhere when forced
    via ``interpret=True``); falls back to the XLA reference path for
    unsupported shapes. ``window``: causal sliding window (Mistral SWA) —
    out-of-window key BLOCKS are skipped, so long-seq FLOPs scale with
    T·window instead of T².
    """
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq, bk = _pick_blocks(tq, tk, d, q.dtype.itemsize, block_q, block_k)
    if not _supported(tq, tk, d, bq, bk) or h % kvh:
        from deepspeed_tpu.models.transformer import dot_product_attention
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            f"flash_attention: shape (tq={tq}, tk={tk}, d={d}, h={h}, "
            f"kvh={kvh}) outside kernel support; using the XLA reference "
            f"path (slower — check block/tile divisibility)")
        return dot_product_attention(q, k, v, causal=causal,
                                     q_offset=q_offset, window=window)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, tk, d)
    bwd_bq, bwd_bk = _pick_bwd_blocks(tq, tk, d, q.dtype.itemsize, bq, bk)
    out = _flash(qf, kf, vf, causal, q_offset, bq, bk, window, interpret,
                 bwd_bq, bwd_bk)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             interpret: Optional[bool] = None):
    """Inference-only flash forward returning (out, lse [B,T,H]) for the
    paged-history merge (ops/paged_attention.merge_attention). No
    custom_vjp — serving never differentiates through it. Falls back to
    the XLA lse-returning reference off-TPU/unsupported shapes."""
    b, tq, h, d = q.shape
    _, tk, kvh, _ = k.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq, bk = _pick_blocks(tq, tk, d, q.dtype.itemsize)
    if not _supported(tq, tk, d, bq, bk) or h % kvh:
        # NOTE: the lse fallback requires kvh | h (GQA group reshape) —
        # it raises a clear error otherwise rather than mis-grouping
        from deepspeed_tpu.ops.paged_attention import \
            causal_attention_with_lse
        return causal_attention_with_lse(q, k, v)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, tk, d)
    out, lse = _fwd(qf, kf, vf, 1.0 / math.sqrt(d), causal, 0, bq, bk,
                    None, interpret)
    out = out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, tq).transpose(0, 2, 1)
    return out, lse


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True,
                            q_offset: int = 0,
                            **kw) -> jax.Array:
    """Mesh-aware flash attention for use inside the jitted train step.

    A bare ``pallas_call`` has no SPMD partitioning rule — under automatic
    sharding XLA would replicate q/k/v onto every chip. This wrapper
    shard_maps the kernel over the batch axes ('data','expert') and, when
    head counts divide, the head axes ('model' for TP and 'seq' for
    Ulysses — sharding heads over 'seq' after a sequence-sharded input IS
    the Ulysses all-to-all, reference sequence/layer.py:331, emitted here
    by the shard_map in_specs resharding). Falls back to the XLA attention
    when the local shapes don't meet the kernel's constraints.
    """
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import ZERO_AXES, get_mesh, has_mesh

    if not has_mesh():
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               **kw)
    mesh = get_mesh()
    b, tq, h, d = q.shape
    kvh = k.shape[2]

    batch_axes = tuple(a for a in ZERO_AXES
                       if mesh.shape[a] > 1 and b % mesh.shape[a] == 0)
    bdiv = 1
    for a in batch_axes:
        bdiv *= mesh.shape[a]
    head_axes = tuple(a for a in ("model", "seq") if mesh.shape[a] > 1)
    hdiv = 1
    for a in head_axes:
        hdiv *= mesh.shape[a]
    # GQA grouping is only correct when q AND kv heads shard identically.
    # Indivisible counts first try the uneven-head treatment (static head
    # padding / minimal KV replication, exact grads — parallel/ulysses.
    # _even_heads, the reference uneven_heads_all2all analogue) so the
    # full head split survives; only exotic shapes degrade.
    orig_h = h
    if head_axes and (h % hdiv or kvh % hdiv):
        from deepspeed_tpu.parallel.ulysses import _even_heads
        evened = _even_heads(q, k, v, hdiv)
        if evened is not None:
            q, k, v, orig_h = evened
            h, kvh = q.shape[2], k.shape[2]
        else:
            head_axes = tuple(a for a in ("model",)
                              if mesh.shape[a] > 1)
            hdiv = mesh.shape["model"] if head_axes else 1
            if head_axes and (h % hdiv or kvh % hdiv):
                head_axes, hdiv = (), 1
    if b % max(bdiv, 1):
        batch_axes, bdiv = (), 1

    if not (batch_axes or head_axes):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               **kw)

    bspec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    hspec = head_axes if len(head_axes) > 1 else \
        (head_axes[0] if head_axes else None)
    spec = P(bspec, None, hspec, None)

    local = partial(flash_attention, causal=causal, q_offset=q_offset, **kw)
    # every mesh axis is named manual, the size-1 ones included: Mosaic
    # lowers only under a fully manual mesh (a partial-manual shard_map
    # fails on the chip with "Mosaic kernels cannot be automatically
    # partitioned"). An axis > 1 the spec does not mention sees replicated
    # operands and computes the same attention on each of its shards.
    # check_vma=False: pallas_call outputs carry no varying-axes metadata;
    # the kernel is embarrassingly parallel over the manual axes anyway
    fn = jax.shard_map(lambda a, b_, c: local(a, b_, c),
                       mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, axis_names=set(mesh.axis_names),
                       check_vma=False)
    out = fn(q, k, v)
    if out.shape[2] != orig_h:
        out = out[:, :, :orig_h, :]   # drop padded query heads
    return out
