"""Paged attention — Pallas TPU kernel over a blocked KV arena.

The TPU-native replacement for the reference FastGen ragged kernels
(deepspeed/inference/v2/kernels/ragged_ops/: blocked_flash, blocked_kv_
rotary, logits_gather). The reference gathers paged KV with CUDA kernels
driven by per-sequence block tables; here the page table is a
scalar-prefetch operand, so each KV block's DMA source address is computed
*from the page table itself* inside the BlockSpec index map — the arena is
never gathered into a contiguous buffer in HBM.

Arena layout: every pool is TOKEN-MAJOR, ``[blocks, block_size, kv_heads *
head_dim]``: a token's heads side by side on the lanes. It is the layout a
row scatter writes in place and the one the kernel reads in place (a page
as it lies is every head's, fetched once for all of them where a program's
VMEM holds them — :func:`heads_per_program` —, and a head's page is the
lane slice ``[kh * d, (kh + 1) * d)``, which is why the heads share the
LAST axis: ``[.., kv_heads, head_dim]`` would tile heads over sublanes), so
no step program relays a pool between its write and its read
(tests/test_tpu_compile.py counts the arena-shaped copies: none). A layer's
region is ``num_blocks + 1`` pages; the last is a TRASH page: padded token
slots and padded page-table entries all point at it, so scatter/gather stay
branch-free and static-shape. Block size and head_dim are chosen to satisfy
the (8, 128) tile rule.

K and V pools may differ in width (``head_dim`` of K, of V). A model whose
layers are of two attention kinds (full and window: different KV head
counts) has a pool per kind and per K/V (:func:`init_arena_typed`). The page
table stays one per sequence. A window layer keeps its whole history in its
pages; its readers visit only the pages the window touches. A LATENT layer
(kind 2, DeepSeek-V3 MLA) has ONE pool: a token's row is ``[c (kv_lora_rank)
; k_rope ; zero lanes up to the pool's width]``, one "KV head" that every
query head reads; the row is the key and its first ``kv_lora_rank`` lanes
are the value, so a reader takes a page once and never splits it
(:func:`write_rows`, ``v_lanes=`` of the XLA readers, :func:`mla_decode`).
A latent stack that PICKS ITS KEYS (``DecoderConfig.layer_indexer``) has a
second pool, ``INDEX_POOL``: an indexer's ONE key a token, a region for
each layer that owns an indexer, under the same page table and the same
writer. Its readers are the last section of this file: a scorer over a
row's pages (:func:`index_scores_paged`), the exact top-k
(:func:`topk_picks` as positions, :func:`topk_mask` as a mask), and the
reads of the latent pool that follow them — BY TOKEN INDEX for a row of
one query (:func:`picked_attention`: the rows a query picked and no
other), under the picks' mask for a chunk's queries (``picked=`` of
:func:`mla_decode` and the XLA history reader).

Two implementations with identical semantics (tested against each other):

- :func:`paged_attention_xla` — gather + masked softmax in pure XLA.
  Works everywhere, reference semantics, used for prefill chunks.
- :func:`paged_attention` — the Pallas kernel; online softmax accumulated
  across the page grid dimension, per-sequence block skipping via the
  prefetched context lengths.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Arena plumbing
# ---------------------------------------------------------------------------

def init_arena(num_layers: int, kv_heads: int, num_blocks: int,
               block_size: int, head_dim: int, dtype=jnp.bfloat16):
    """Paged KV arena with one extra trash block per layer.

    Returns {"k": A, "v": A} with A: [L*(num_blocks+1), bs, kvh*dh] —
    ONE flat block pool for all layers (layer l's logical block b lives at
    l*(num_blocks+1)+b; see :func:`layer_page_offset`). Flat so the
    engine's layer scan can thread the WHOLE arena as a carry and update
    it in place — a per-layer stacked arena would ride the scan as
    xs/ys, which cannot alias, forcing XLA to copy the full (multi-GB)
    arena every decode step. The one-kind case of
    :func:`init_arena_typed`.
    """
    return init_arena_typed((0,) * num_layers, {0: kv_heads}, num_blocks,
                            block_size, head_dim, head_dim, dtype)


#: pool names of a typed arena by attention kind (0 full, 1 window, 2
#: latent: one pool, the row is K and its leading lanes are V)
KIND_POOLS = {0: ("k", "v"), 1: ("k_win", "v_win"), 2: ("latent",)}
#: the pool of the index keys beside a latent stack's one pool: ONE key of
#: ``index_head_dim`` a token in each layer that OWNS an indexer (the i-th
#: owner's pages are ``i*(num_blocks+1) + b``), the page table the other
#: pools have
INDEX_POOL = "index"


def init_arena_typed(layer_kinds, kv_heads_by_kind: dict, num_blocks: int,
                     block_size: int, k_width: int, v_width: int,
                     dtype=jnp.bfloat16, index_layers: int = 0,
                     index_width: int = 0) -> dict:
    """The arena of a typed layer stack: a FLAT dict of pools, one per
    attention kind present and per K/V (``KIND_POOLS``), each
    ``[layers of the kind * (num_blocks + 1), bs, kv_heads of the kind *
    width]`` — :func:`init_arena`'s flat block numbering within a kind: the
    i-th layer OF ITS KIND owns pages ``i*(num_blocks+1) + b``. One page
    table addresses every pool (logical page b is the same tokens in
    all). The latent kind's one pool is ``k_width`` lanes wide (its row is
    the key; ``v_width`` is not used). A layer with no attention (a
    state-space mixer, 3; no mixer at all, -1) has no pages.
    ``index_layers`` layers that own an indexer add ``INDEX_POOL``, a
    region each, ``index_width`` lanes a token."""
    arena = {}
    if index_layers:
        arena[INDEX_POOL] = jnp.zeros(
            (index_layers * (num_blocks + 1), block_size, index_width),
            dtype)
    for kind in sorted(set(layer_kinds) & set(KIND_POOLS)):
        pages = sum(1 for a in layer_kinds if a == kind) * (num_blocks + 1)
        if kind == 2:
            arena[KIND_POOLS[kind][0]] = jnp.zeros(
                (pages, block_size, k_width), dtype)
            continue
        kname, vname = KIND_POOLS[kind]
        kvh = kv_heads_by_kind[kind]
        arena[kname] = jnp.zeros((pages, block_size, kvh * k_width), dtype)
        arena[vname] = jnp.zeros((pages, block_size, kvh * v_width), dtype)
    return arena


def layer_page_offset(layer: jax.Array, num_blocks: int) -> jax.Array:
    """Absolute block id offset of ``layer``'s region in the flat pool."""
    return layer * (num_blocks + 1)


def row_slots(starts: jax.Array, counts: jax.Array, c: int):
    """The slots of a chunk in ROWS ``[n, c]``: ``(row, pos, valid)``, each
    ``[n, c]`` — slot ``(i, j)`` is token ``starts[i] + j`` of sequence
    ``i`` and holds a token where ``j < counts[i]``. What
    :func:`write_kv` / :func:`write_rows` take for tokens that arrive as
    rows (every decode program: ``c == 1``)."""
    n = starts.shape[0]
    col = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None], (n, c))
    row = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, c))
    return row, starts[:, None] + col, col < counts[:, None]


def write_kv(arena_k: jax.Array, arena_v: jax.Array, k: jax.Array,
             v: jax.Array, page_table: jax.Array, row: jax.Array,
             pos: jax.Array, valid: jax.Array, trash_block=None):
    """Scatter new KV into the arena, ONE update a token slot, each
    token's row (its heads side by side) written whole.

    arena_k/arena_v: [NB, bs, kvh * d] (one layer's region of the flat
    pool, or the whole pool with absolute page-table ids); k/v: [*S, kvh,
    d], a token a slot, in whatever form the step holds its tokens —
    packed ``[1, T, ..]`` or rows ``[n, c, ..]`` (:func:`row_slots`) — and
    ``row`` / ``pos`` / ``valid`` [*S] say of each slot whose token it
    is, at which position of its sequence, and whether it holds one at
    all. page_table: [n, mb] physical block ids (padded entries may be
    anything). A slot that holds no token routes to ``trash_block``
    (default the pool's last block) BY ``valid``: a packed slot past the
    batch's tokens carries a clipped ``row`` / ``pos`` that alias a live
    token's.
    """
    bi, oi = _token_slots(arena_k.shape, page_table, row, pos, valid,
                          trash_block)
    return (arena_k.at[bi, oi].set(
                k.reshape(bi.shape[0], -1).astype(arena_k.dtype),
                mode="drop"),
            arena_v.at[bi, oi].set(
                v.reshape(bi.shape[0], -1).astype(arena_v.dtype),
                mode="drop"))


def _token_slots(pool_shape, page_table: jax.Array, row: jax.Array,
                 pos: jax.Array, valid: jax.Array, trash_block):
    """(page, offset) of every token slot, flattened: the scatter
    :func:`write_kv` and :func:`write_rows` share."""
    nbp1, bs, _ = pool_shape
    if trash_block is None:
        trash_block = nbp1 - 1
    row, pos, valid = (a.reshape(-1) for a in (row, pos, valid))
    phys = page_table[row, jnp.minimum(pos // bs, page_table.shape[1] - 1)]
    return jnp.where(valid, phys, trash_block), pos % bs            # → trash


def write_rows(pool: jax.Array, rows: jax.Array, page_table: jax.Array,
               row: jax.Array, pos: jax.Array, valid: jax.Array,
               trash_block=None):
    """:func:`write_kv` for a pool that holds ONE tensor a token (a latent
    layer's): rows [*S, w] with ``w`` at most the pool's lanes; the lanes
    past ``w`` are written zero."""
    w = rows.shape[-1]
    bi, oi = _token_slots(pool.shape, page_table, row, pos, valid,
                          trash_block)
    rows = rows.reshape(-1, w).astype(pool.dtype)
    if pool.shape[-1] > w:
        rows = jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - w)))
    return pool.at[bi, oi].set(rows, mode="drop")


def copy_pages(arena: dict, src: jax.Array, dst: jax.Array,
               stride: int) -> dict:
    """Copy whole KV pages ``src[i] → dst[i]`` across every layer's region.

    The copy-on-write half of prefix caching: page tables are plain
    device arrays of physical page ids, so "sharing" a cached prefix page
    is just listing the same id in two sequences' tables — the paged
    kernels read through the table and never care who owns a page. The
    only time bytes move is when a PARTIAL cached page must be
    duplicated before its new owner appends into it, which is this op:
    one gather+scatter over each flat pool.

    arena: pools [L_pool*(nb+1), bs, kvh*d] (every pool of the dict, each
    with its own layer count); src/dst: [m] logical page ids (< nb,
    layer-relative); ``stride`` = nb + 1, a layer's pages.
    """
    out = {}
    for name, pool in arena.items():
        offs = jnp.arange(pool.shape[0] // stride,
                          dtype=jnp.int32)[:, None] * stride
        s = (offs + jnp.asarray(src, jnp.int32)[None, :]).reshape(-1)
        d = (offs + jnp.asarray(dst, jnp.int32)[None, :]).reshape(-1)
        out[name] = pool.at[d].set(pool[s])
    return out


# ---------------------------------------------------------------------------
# XLA reference path (also the prefill path)
# ---------------------------------------------------------------------------

def _gather_pages(arena: jax.Array, page_table: jax.Array, kv_heads: int):
    """[nb+1, bs, kvh*d] x [n, mb] → [n, mb*bs, kvh, d]: the tokens as the
    pool holds them."""
    n, mb = page_table.shape
    bs = arena.shape[1]
    return arena[page_table].reshape(n, mb * bs, kv_heads, -1)


def _masked_attention(q: jax.Array, kg: jax.Array, vg: jax.Array,
                      mask: jax.Array, with_lse: bool,
                      scale: Optional[float] = None):
    """Shared gathered-softmax core: q [n,c,h,dk], kg [n,S,kvh,dk], vg
    [n,S,kvh,dv] (a token's heads side by side, as the pools hold them),
    mask broadcastable to [n,kvh,g,c,S]. Returns out [n,c,h,dv] (+ lse
    [n,c,h] fp32 when with_lse; a row with no visible key gives lse ≈
    -1e30, a weight of 0 in a merge). ``scale``: the scores' factor,
    default ``dk ** -0.5`` (a caller that zero-pads the heads passes the
    true width's).

    One query a row (``c == 1``, a decode step) leaves the keys' lanes
    whole: q is spread block-diagonally over the kv heads' lanes and a row
    is one ``[h, kvh*dk] x [kvh*dk, S]`` matmul, its result's own-head
    lanes picked afterwards. Splitting the lanes into ``[kvh, d]`` for a
    matmul a head relays every gathered page (a head is a tile COLUMN of a
    token-major page), which at a decode step's four query rows a head is
    most of the read: 1.96 → 1.15 ms a layer at 64 rows of 8 pages on a
    v5e (PERF.md §6, PR 34). The zeros cost ``kvh`` times the FLOPs, which
    a decode row has to spare and a chunk has not: ``c > 1`` contracts a
    head at a time."""
    n, c, h, dh = q.shape
    S, kvh = kg.shape[1:3]
    if h % kvh:
        raise ValueError(f"GQA requires kv heads to divide q heads "
                         f"(h={h}, kvh={kvh})")
    groups = h // kvh
    lanes_whole = c == 1
    qg = q.reshape(n, c, kvh, groups, dh)
    if lanes_whole:
        eye = jnp.eye(kvh, dtype=q.dtype)
        q_bd = qg[:, 0, :, :, None, :] * eye[:, None, :, None]  # [n,k,g,k',d]
        s = jnp.einsum("nrl,nsl->nrs", q_bd.reshape(n, h, kvh * dh),
                       kg.reshape(n, S, kvh * dh).astype(q.dtype),
                       preferred_element_type=jnp.float32) \
            .reshape(n, kvh, groups, 1, S)
    else:
        s = jnp.einsum("nckgd,nksd->nkgcs", qg,
                       kg.transpose(0, 2, 1, 3).astype(q.dtype),
                       preferred_element_type=jnp.float32)
    s = s / math.sqrt(dh) if scale is None else s * scale
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                     # [n,k,g,c]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    if lanes_whole:
        full = jnp.einsum("nrs,nsl->nrl", p.reshape(n, h, S).astype(vg.dtype),
                          vg.reshape(n, S, -1))                # [n,h,k'*dv]
        out = jnp.einsum("nkgjd,kj->nkgd",
                         full.reshape(n, kvh, groups, kvh, -1),
                         eye.astype(full.dtype))[:, None]       # [n,1,k,g,dv]
    else:
        out = jnp.einsum("nkgcs,nksd->nckgd", p.astype(vg.dtype),
                         vg.transpose(0, 2, 1, 3))
    out = out / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    out = out.reshape(n, c, h, vg.shape[-1]).astype(q.dtype)
    if not with_lse:
        return out
    lse = m + jnp.log(jnp.maximum(l, 1e-30))                    # [n,k,g,c]
    return out, lse.transpose(0, 3, 1, 2).reshape(n, c, h)


def _span_pages(mb: int, span: int, bs: int) -> int:
    """The pages ``span`` positions can touch wherever they start, at most
    the table's ``mb``."""
    return min(mb, (max(span, 1) + bs - 2) // bs + 1)


def _window_pages(page_table: jax.Array, lowest: jax.Array, span: int,
                  bs: int):
    """The pages a window touches: ``lowest`` [n] is each row's lowest
    visible key position (may be negative), ``span`` the most positions a
    row's queries see from there. Returns (page ids [n, np], key
    positions [n, np*bs]); a page past the table's width repeats the last
    entry under positions no query can see."""
    n, mb = page_table.shape
    pages = _span_pages(mb, span, bs)
    first = jnp.maximum(lowest, 0) // bs                          # [n]
    idx = first[:, None] + jnp.arange(pages, dtype=jnp.int32)[None]
    ids = jnp.take_along_axis(page_table, jnp.minimum(idx, mb - 1), axis=1)
    kpos = (idx[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
            ).reshape(n, pages * bs)
    far = jnp.iinfo(jnp.int32).max
    return ids, jnp.where(jnp.repeat(idx < mb, bs, axis=1), kpos, far)


def _gather_kv(arena_k, arena_v, ids, kvh: int, v_lanes: Optional[int]):
    """The gathered keys and values of the readers below. ``v_lanes``: a
    latent pool, whose row is the key and whose first ``v_lanes`` lanes are
    the value: ONE gather, the values a lane slice of it."""
    kg = _gather_pages(arena_k, ids, kvh)
    if v_lanes is None:
        return kg, _gather_pages(arena_v, ids, kvh)
    return kg, kg[..., :v_lanes]


def paged_attention_xla(q: jax.Array, arena_k: jax.Array,
                        arena_v: Optional[jax.Array], page_table: jax.Array,
                        starts: jax.Array, counts: jax.Array,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        with_lse: bool = False,
                        v_lanes: Optional[int] = None):
    """Gather-then-attend over the paged arena (reference semantics).

    q: [n, c, H, dk] (query rows j >= counts[i] give garbage rows — the
    caller discards them); arena: [nb+1, bs, kvh * dk / dv]; page_table:
    [n, mb]; starts/counts: [n]. Returns [n, c, H, dv] (and lse [n, c, H]
    with ``with_lse``). ``window``: key j is visible to query i only when
    ``i - j < window``, and only the pages such keys lie in are gathered.
    ``v_lanes``: ``arena_k`` is a latent pool (``arena_v`` None), q as wide
    as its rows, one KV head; the output is ``v_lanes`` wide.
    """
    bs = arena_k.shape[1]
    n, c = q.shape[:2]
    mb = page_table.shape[1]
    qpos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None]  # [n, c]
    ctx = starts + counts                                          # [n]
    if window is None:
        ids = page_table
        kpos = jnp.arange(mb * bs, dtype=jnp.int32)                # [S]
        mask = (kpos[None, None] <= qpos[..., None]) & \
            (kpos[None, None] < ctx[:, None, None])                # [n, c, S]
    else:
        ids, kpos = _window_pages(page_table, starts - (window - 1),
                                  window + c - 1, bs)
        kpos = kpos[:, None]                                       # [n, 1, S]
        mask = (kpos <= qpos[..., None]) & (kpos < ctx[:, None, None]) & \
            (kpos > qpos[..., None] - window)
    kvh = arena_k.shape[-1] // q.shape[-1]
    return _masked_attention(
        q, *_gather_kv(arena_k, arena_v, ids, kvh, v_lanes),
        mask[:, None, None], with_lse, scale)


def paged_attention_hist_xla(q: jax.Array, arena_k: jax.Array,
                             arena_v: Optional[jax.Array],
                             page_table: jax.Array, starts: jax.Array,
                             window: Optional[int] = None,
                             scale: Optional[float] = None,
                             v_lanes: Optional[int] = None,
                             picked: Optional[jax.Array] = None):
    """HISTORY-only attention: row i's queries attend keys [0, starts[i])
    — the tokens already in the arena BEFORE the current chunk's write.
    Returns (out [n,c,h,dh], lse [n,c,h] fp32).

    Reading the pre-write arena is what breaks the per-layer write→read
    dependency XLA otherwise serializes (engine_v2.ragged_forward); the
    within-chunk causal part is computed separately and merged by
    logsumexp. Empty-history rows produce lse ≈ -1e30, so their (garbage)
    out vanishes in the merge — no special-casing for fresh rows mixed
    into a continuation batch. ``window``: query j of a row (position
    ``starts + j``) sees only the history keys within the window, and only
    the pages those lie in are gathered. ``v_lanes``: a latent pool, as
    :func:`paged_attention_xla`. ``picked`` [n, c, mb * bs] bool (no
    window): of those keys, the ones each query's indexer kept.
    """
    bs = arena_k.shape[1]
    mb = page_table.shape[1]
    if window is None:
        ids = page_table
        kpos = jnp.arange(mb * bs, dtype=jnp.int32)
        mask = (kpos[None, :] < starts[:, None])[:, None, None, None, :]
        if picked is not None:
            mask = mask & picked[:, None, None]
    else:
        ids, kpos = _window_pages(page_table, starts - (window - 1),
                                  window - 1, bs)
        qpos = starts[:, None] + jnp.arange(q.shape[1],
                                            dtype=jnp.int32)[None]  # [n, c]
        kpos = kpos[:, None]                                        # [n,1,S]
        mask = ((kpos < starts[:, None, None]) &
                (kpos > qpos[..., None] - window))[:, None, None]
    kvh = arena_k.shape[-1] // q.shape[-1]
    return _masked_attention(
        q, *_gather_kv(arena_k, arena_v, ids, kvh, v_lanes), mask, True,
        scale)


def merge_attention(out_a, lse_a, out_b, lse_b, sink=None):
    """Combine two attention partials over DISJOINT key sets via their
    logsumexps (the flash-attention merge): outs [n,c,h,dh], lses
    [n,c,h] → merged out. ``sink`` [h]: a learned logit that joins the
    softmax as one more column — it takes mass (the denominator grows by
    ``exp(sink)``) and gives no value."""
    m = jnp.maximum(lse_a, lse_b)
    if sink is not None:
        sink = sink.astype(jnp.float32)
        m = jnp.maximum(m, sink)
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    total = wa + wb if sink is None else wa + wb + jnp.exp(sink - m)
    denom = jnp.maximum(total, 1e-30)[..., None]
    return (out_a.astype(jnp.float32) * wa[..., None]
            + out_b.astype(jnp.float32) * wb[..., None]) / denom


def causal_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                              window: Optional[int] = None,
                              scale: Optional[float] = None,
                              picked: Optional[jax.Array] = None):
    """Plain causal attention over one chunk returning (out, lse) for the
    history merge — XLA path ([n,c,h,dh] layout, GQA via head groups; K
    and V may differ in width). ``window``: key j visible to query i only
    when ``i - j < window``. ``picked`` [n, c, c] bool: of the chunk's
    keys, the ones each query's indexer kept (a query that kept none of
    them gives an lse of about -1e30, a weight of 0 in a merge)."""
    c = q.shape[1]
    i = jnp.arange(c, dtype=jnp.int32)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask = mask & (i[None, :] > i[:, None] - window)
    mask = mask[None, None, None]
    if picked is not None:
        mask = mask & picked[:, None, None]
    return _masked_attention(q, k, v, mask, True, scale)


def one_key_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                               scale: Optional[float] = None):
    """:func:`causal_attention_with_lse` of chunks of ONE token ([n, 1, h,
    dk], [n, 1, kvh, dk], [n, 1, kvh, dv]) in closed form: a query whose
    only key is its own has ``out = v`` (the KV head's, for each query head
    of its group) and ``lse = scale * q·k``, float32 — no softmax, no
    matmul over keys."""
    n, _, h, dh = q.shape
    kvh = k.shape[2]
    qg = q.reshape(n, 1, kvh, h // kvh, dh)
    s = jnp.einsum("nckgd,nckd->nckg", qg, k.astype(q.dtype),
                   preferred_element_type=jnp.float32).reshape(n, 1, h)
    s = s / math.sqrt(dh) if scale is None else s * scale
    return jnp.repeat(v, h // kvh, axis=2).astype(q.dtype), s


# ---------------------------------------------------------------------------
# Pallas kernel (decode / short-chunk path)
# ---------------------------------------------------------------------------

def tile_queries(c: int, groups: int) -> int:
    """``TILE_Q``: the queries of a row's SMALL tile — what a row of at most
    that many live queries (a decode row riding in a chunk-wide step: one)
    computes a page turn in place of its whole ``groups * c`` block. The
    fewest queries, a power of two, whose ``TILE_Q * groups`` matmul rows
    fill whole bf16 sublane tiles (16 rows: 4 queries at 4 queries a KV
    head, 2 at 8, 1 at 16): a turn's cost grows with the tile's rows
    (docs/kernels.md, PR 38), and the rows a chunk-wide step carries
    beside its prompt chunks hold ONE live query. The whole chunk where no
    such tile divides it."""
    t = 1
    while t < c and (c % t or (t * groups) % 16):
        t *= 2
    return t if c % t == 0 else c


#: what a program's blocks, page buffers and carried values may take of VMEM
#: for it to hold more than one KV head (:func:`heads_per_program`): a
#: quarter of the 16 MiB a v5e kernel is scoped to. Blocks of up to 64 matmul
#: rows (a decode row's, a row of one query) take all of 8 heads under it, 128
#: rows 4, 256 rows 2; a chunk's 512 rows and up walk a head a program, as
#: before PR 47. VMEM itself holds more (10 MiB: 4 heads at 512 rows, 2 at
#: 1,024), but there a turn is its matmuls (1.1 us a head at 512 live rows
#: beside a fetch's fixed 0.36), and every head a program holds is one more
#: body to trace and lower in each step program of a replica's set-up: at 10
#: MiB one call moved (8 rows of a whole live chunk, 0.52 → 0.43 ms) and the
#: chat cell's ``setup_s`` rose 3.8 s of 40 for 1.7 at 4 MiB (my chip runs,
#: PR 47)
_FUSED_VMEM_BYTES = 4 * 2 ** 20


def heads_per_program(rows: int, kv_heads: int, k_lanes: int, v_lanes: int,
                      block_size: int, itemsize: int = 2) -> int:
    """``hp``: the KV heads of one row that ONE program of
    :func:`_paged_kernel` holds — a page is fetched once for them, ``hp *
    (k_lanes + v_lanes)`` lanes of it in one copy a pool. The largest
    divisor of ``kv_heads`` whose VMEM fits ``_FUSED_VMEM_BYTES``: the q /
    out / lse blocks (``rows`` = queries a KV head x the chunk; each
    double-buffered by the pipeline, rows padded to a sublane tile, the
    lse's one lane to 128), the two page buffers a pool, the float32
    accumulator, maximum and sum each head carries through the walk, and
    one head's scores and probabilities. A function of the call's shapes
    alone (``itemsize``: of q, K and V); what the kernel's wrapper and the
    engine's accounting (``dispatch/kv_page_fetches``) both ask. A decode
    row's block and a row of one query (4-16 matmul rows) take every head;
    a chunk's 512 rows and up one — the walk as it was before the heads
    shared a fetch."""
    def padded(tile):
        return -(-rows // tile) * tile

    def vmem(hp):
        blocks = 2 * hp * (padded(32 // itemsize) * (k_lanes + v_lanes) *
                           itemsize + padded(8) * 128 * 4)
        page_buffers = 2 * block_size * hp * (k_lanes + v_lanes) * itemsize
        carried = hp * padded(8) * (v_lanes + 2 * 128) * 4
        return blocks + page_buffers + carried + \
            2 * padded(8) * block_size * 4

    return max(hp for hp in range(1, kv_heads + 1) if kv_heads % hp == 0
               and (hp == 1 or vmem(hp) <= _FUSED_VMEM_BYTES))


# jitted: a program of the kernel below takes this step once a head and
# walk instance, and the step programs hold the kernel at a dozen shapes of
# rows — one trace serves them all (a serving replica's set-up traces and
# lowers every step program: 21 in the benchmark's chat cell)
@functools.partial(jax.jit, static_argnames=("scale",))
def _softmax_step(q, k_blk, v_blk, visible, acc, m_prev, l_prev, *, scale):
    """One online-softmax step of ONE KV head inside :func:`_paged_kernel`:
    q [r, dk] over a page's keys [bs, dk] and values [bs, dv] under
    ``visible`` [r, bs] → the head's carried (accumulator [r, dv], maximum
    [r, 1], sum [r, 1]), float32."""
    s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # float mask arithmetic: a row that has seen no key yet keeps p and its
    # correction at exactly 0
    alive = (m_new > _NEG_INF / 2).astype(jnp.float32)
    p = jnp.exp(s - m_new) * alive
    corr = jnp.exp(m_prev - m_new) * alive
    acc = acc * corr + lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc, m_new, l_prev * corr + jnp.sum(p, axis=1, keepdims=True)


def _paged_kernel(pt_ref, starts_ref, counts_ref, qcounts_ref, q_ref, k_hbm,
                  v_hbm, o_ref, *rest, block_size: int, groups: int,
                  tile_q: int, scale: float, mb: int,
                  with_lse: bool = False, window: Optional[int] = None):
    """Grid (n_seq, kvh / hp): ONE program per sequence and group of ``hp``
    KV heads that walks this sequence's pages with double-buffered manual
    DMAs from the HBM-resident arena, each page fetched ONCE for the
    program's heads.

    A (seq, head, page) grid would be thousands of sequential tiny
    programs per layer (measured 310 ms vs 1.5 ms per 1B-model decode
    step); here pages are an in-kernel ``fori_loop`` with the next page's
    DMAs in flight while the current one computes — the reference
    blocked_flash/paged-KV structure.

    q_ref block: [1, hp, rows, dk], QUERY-MAJOR (row = j * groups + g: query
    j of the chunk, head g of the KV head's group), so a row's live queries
    ``j < qcounts[s]`` are the LEADING rows of each head's block;
    k_hbm/v_hbm: the FULL arena [NB, bs, kvh * d] left in ANY/HBM memory
    space (a token's heads side by side, the layout a row scatter writes
    without a relayout), of which the program's heads are the lanes ``[kh0
    * d, (kh0 + hp) * d)`` — with ``hp == kvh`` the page as it lies, one
    contiguous copy —; k_buf/v_buf: [2, bs, hp * d] VMEM double
    buffers, head ``h`` of the program the static lane slice ``[h * d, (h
    + 1) * d)`` of them (whole 128-lane tiles). With ``with_lse`` an extra
    [1, hp, rows, 1] f32 output carries each row's logsumexp (the
    partial-attention merge needs it — a split step's history part).
    K and V may differ in width (the output is dv wide). ``window``
    (static): key j is visible to query i only when ``i - j < window``,
    and the walk STARTS at the page that holds the lowest key any query of
    the row can see.

    The work follows the row's LIVE queries, and a page is fetched once
    either way: a row of at most ``tile_q`` live queries walks its pages
    with ONE tile of ``tile_q * groups`` matmul rows a head (scores, mask,
    softmax and both matmuls over those rows alone), a row of more with its
    whole block — two instances of one walk, of which a program runs one.
    Every query past the live ones, and every query of a row with no live
    query or no visible page, gets zeros and an lse of -1e30 (a weight of 0
    in :func:`merge_attention`).

    On a v5e (``tools/bench_paged_hist.py --groups --sweep``: the kernel
    alone with the wrapper's reshapes, seed 3800000011, the parent's file
    beside this one's in one process; my chip runs, PR 47), us a PAGE — all
    of a row's KV heads — (ms a call), a page fetched a head at a time →
    this kernel:

    - Mistral-7B (64 rows, 32 / 8 heads of 128, contexts 128–2,500).
      Every row as ONE query, ``[64, 1]`` (a grouped split step's call,
      607 pages): 3.78 → 1.26 (2.29 → 0.767); the decode programs'
      ``paged_attn`` (624 pages): 3.71 → 1.20 (2.32 → 0.751). By ``hp`` 1 /
      2 / 4 / 8: 3.81 / 2.22 / 1.61 / 1.28 and 3.71 / 2.16 / 1.55 / 1.22 —
      a turn costs 0.36 + 0.113 x hp us.
    - Blocks of a chunk's rows keep ``hp`` 1 (``_FUSED_VMEM_BYTES``). What
      more would buy, the same runs: Mistral's row form ``[64, 128]`` (512
      rows), 61 rows of one live query + 3 of 128, by ``hp`` 1 / 2 / 4:
      5.34 / 3.65 / 3.00 (3.31 / 2.27 / 1.86), 8: out of VMEM; all 128
      live 8.97 → 6.79 at 4; 8 rows of a whole live chunk (the long-prompt
      cell) 0.519 / 0.445 / 0.432 / 0.408 ms a call of 48 pages; the chunk
      group ``[8, 128]`` with 3 live rows 0.44 / 0.45 / 0.43 / 0.43 (its
      blocks). MiMo-V2.5's window layer's row form (1,024 rows) 3.48 →
      3.24 at 2, 4: out of VMEM; its full layer's and Command A+'s 2,048
      rows take no second head.
    - MiMo-V2.5 (64 query heads, K 256 / V 128 lanes), ``[64, 1]``: the
      window-128 layer (8 KV heads, 111 pages) 0.724 → 0.468 ms, the full
      layer (4 KV heads, 206 pages) 0.581 → 0.440.

    With NO live query a row-form call is 0.91 / 2.75 / 2.83 / 0.88 / 0.44
    ms (Mistral, MiMo-V2.5 window, full, Command A+, Mistral's 8-row
    program) on both: the grid and the q / out / lse blocks. What
    bounds ``[64, 1]`` now (the same call with the compute or the copies
    taken out): the copies alone 0.560 ms (0.92 us a page of 512 KB: 555
    GB/s with each program's first, exposed fetch inside), the eight heads'
    arithmetic alone 0.690 (0.14 us a head and page), together 0.767 — 51%
    of the call's HBM roofline (607 x 512 KB at 819 GB/s = 0.389 ms; 17%
    before). Measured and NOT kept: several pages a loop turn (as
    ``mla_decode`` below) — 2 / 4 pages read 0.751 / 0.709 ms for 0.767
    on ``[64, 1]`` (-2.1 / -7.5%), 1.79 / 1.74 for 1.86 on the row form,
    0.437 / 0.469 for 0.435 on the chunk group: eight heads already share
    a turn's fixed cost."""
    hp, rows, dk = q_ref.shape[1:]
    dv = o_ref.shape[3]
    if with_lse:
        lse_ref, *rest = rest
    k_buf, v_buf, sem = rest
    s_idx = pl.program_id(0)
    hg = pl.program_id(1)
    start = starts_ref[s_idx]
    ctx = start + counts_ref[s_idx]
    qcount = qcounts_ref[s_idx]
    npages = jnp.minimum(lax.div(ctx + block_size - 1,
                                 jnp.int32(block_size)), mb)
    if window is None:
        first, first_slot = 0, 0
    else:
        first = lax.div(jnp.maximum(start - (window - 1), 0),
                        jnp.int32(block_size))
        first_slot = lax.rem(first, 2)
    live = (npages > first) & (qcount > 0)

    def heads_page(hbm, buf, page):
        width = buf.shape[-1]
        if width == hbm.shape[-1]:
            return hbm.at[page]
        return hbm.at[page, :, pl.ds(pl.multiple_of(hg * width, 128), width)]

    def copies(slot, page_i=None):
        """The DMAs of the row's page ``page_i`` into ``slot``, K's and V's
        (no page: copies of their shape, to wait on)."""
        page = 0 if page_i is None else pt_ref[s_idx, page_i]
        return [pltpu.make_async_copy(heads_page(hbm, buf, page),
                                      buf.at[slot], sem.at[p, slot])
                for p, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def write_dead(lo):
        """Zeros and -1e30 for every head's rows from ``lo`` on."""
        o_ref[0, :, lo:, :] = jnp.zeros((hp, rows - lo, dv), o_ref.dtype)
        if with_lse:
            lse_ref[0, :, lo:, :] = jnp.full((hp, rows - lo, 1), _NEG_INF,
                                             jnp.float32)

    def walk(r):
        """Each head's first ``r`` matmul rows over the row's pages."""
        for copy in copies(first_slot, first):
            copy.start()
        q = [q_ref[0, h, :r, :] for h in range(hp)]         # [r, dk] each
        # the chunk offset of each matmul row's query
        j = lax.div(lax.broadcasted_iota(jnp.int32, (r, 1), 0),
                    jnp.int32(groups))
        qpos = start + j

        def body(b, carry):
            slot = lax.rem(b, 2)

            @pl.when(b + 1 < npages)
            def _prefetch():
                for copy in copies(lax.rem(b + 1, 2), b + 1):
                    copy.start()

            for copy in copies(slot):
                copy.wait()
            kpos = b * block_size + \
                lax.broadcasted_iota(jnp.int32, (r, block_size), 1)
            visible = (kpos <= qpos) & (kpos < ctx)
            if window is not None:
                visible = visible & (kpos > qpos - window)
            return tuple(
                _softmax_step(q[h], k_buf[slot, :, h * dk:(h + 1) * dk],
                              v_buf[slot, :, h * dv:(h + 1) * dv], visible,
                              *head, scale=scale)
                for h, head in enumerate(carry))

        heads = lax.fori_loop(
            first, npages, body,
            ((jnp.zeros((r, dv), jnp.float32),
              jnp.full((r, 1), _NEG_INF, jnp.float32),
              jnp.zeros((r, 1), jnp.float32)),) * hp)
        is_live = j < qcount
        for h, (acc, m, l) in enumerate(heads):
            l = jnp.maximum(l, 1e-30)
            o_ref[0, h, :r, :] = jnp.where(is_live, acc / l, 0.0) \
                .astype(o_ref.dtype)
            if with_lse:
                lse_ref[0, h, :r, :] = jnp.where(
                    is_live & (m > _NEG_INF / 2), m + jnp.log(l), _NEG_INF)
        if r < rows:
            write_dead(r)

    whole = live
    if tile_q * groups < rows:
        pl.when(live & (qcount <= tile_q))(lambda: walk(tile_q * groups))
        whole = live & (qcount > tile_q)
    pl.when(whole)(lambda: walk(rows))
    pl.when(jnp.logical_not(live))(lambda: write_dead(0))


#: the kernel's name where a typed stack's DECODE program reads through it
#: (``engine_v2._ragged_forward_typed``): not ``paged_attn_lse*``, which a
#: trace's readers take for a split step's history
DECODE_KERNEL = "paged_attn_decode"

#: what the XLA reader would COPY for a decode program's rows in one layer
#: (:func:`decode_reads_by_kernel`) from which that program reads through
#: the kernel. Both readers' time grows with the rows: the gather copies and
#: reads again every row's whole table (a window kind: its window's pages),
#: the kernel walks the live pages at about 4 us a program before its first
#: page arrives. At 100 MB (MiMo-V2.5's window kind, 64 rows of two pages)
#: they are level — 0.29 | 0.33 ms a layer in the cell's decode program,
#: 0.30 | 0.20 as eight chained reads alone — and past it the kernel wins
#: (0.28 | 0.85 at 201 MB, 0.47 | 2.41 at 537: docs/kernels.md, PR 61);
#: under it a program's few rows gather in tens of microseconds, and a
#: kernel body more in the program is 0.6 s of a replica's set-up for each
#: of its row buckets (cell 4: +8.6 s of a 93 s set-up with the kernel in
#: all seven decode programs; my chip runs, PR 61)
DECODE_KERNEL_BYTES = 64 * 2 ** 20


def decode_reads_by_kernel(rows: int, table_pages: int,
                           window: Optional[int], block_size: int,
                           token_bytes: int) -> bool:
    """Whether a typed stack's decode program of ``rows`` rows reads a layer
    of this kind through the kernel: what :func:`paged_attention_xla` would
    copy of the pools for it — every row's ``table_pages`` (a window kind:
    the pages its window can touch) of ``block_size`` tokens of
    ``token_bytes`` (a token's K and V, all KV heads) — is at least
    ``DECODE_KERNEL_BYTES``. A function of the program's shapes alone, which
    the engine's program and its accounting (``launch_work.kv_page_work``)
    both ask."""
    pages = table_pages if window is None else \
        _span_pages(table_pages, window, block_size)
    return rows * pages * block_size * token_bytes >= DECODE_KERNEL_BYTES


#: a head of HALF a lane tile (LFM2's 64): the kernel below slices a head as
#: whole 128-lane tiles of a page, so two KV heads are read as ONE of 128
#: lanes (:func:`pairs_heads`)
HALF_TILE = 64


def pairs_heads(head_dim: int, v_dim: int, kv_heads: int) -> bool:
    """The paged kernel takes these heads two a lane tile: K and V heads of
    ``HALF_TILE`` lanes, an even number of them. The pools keep a token's
    heads side by side UNPADDED (``kv_heads * 64`` lanes: a head padded to
    128 doubles the bytes a token holds and every page read); the kernel
    sees ``kv_heads / 2`` heads of 128 lanes, each the group of BOTH its
    halves' queries (:func:`_pair_queries`)."""
    return head_dim == v_dim == HALF_TILE and kv_heads % 2 == 0


def _pair_queries(q: jax.Array, kvh: int) -> jax.Array:
    """q [n, c, h, 64] → [n, c, h, 128]: the query of a head whose KV head
    is the LOW half of its pair in lanes [0, 64), of the high half in [64,
    128), zeros in the other — its dot with the pair's 128 lanes is its dot
    with its own KV head, exactly (the zeros add 0.0), at twice the
    multiply-adds of a matmul that a page's fetch outlasts."""
    n, c, h, d = q.shape
    own = jnp.eye(2, dtype=q.dtype).reshape(2, 1, 2, 1)
    q = q.reshape(n, c, kvh // 2, 2, h // kvh, 1, d) * own
    return q.reshape(n, c, h, 2 * d)


def _unpair_outputs(out: jax.Array, kvh: int) -> jax.Array:
    """[n, c, h, 128] → [n, c, h, 64]: ``p·V`` over the pair's lanes is
    ``[p·V_low | p·V_high]``; a head keeps its own KV head's half."""
    n, c, h, d2 = out.shape
    out = out.reshape(n, c, kvh // 2, 2, h // kvh, 2, d2 // 2)
    return jnp.stack([out[:, :, :, 0, :, 0], out[:, :, :, 1, :, 1]],
                     axis=3).reshape(n, c, h, d2 // 2)


# jitted (as mla_decode is): a program whose layers are unrolled calls the
# kernel once a layer and row group, and a pallas_call traces its kernel
# body anew every call — under jit the calls of one shape share ONE trace
# and one lowered function (0.15-0.25 s a call on the serving host; the
# typed 64-row split program calls it 21 times for 6 shapes)
@functools.partial(jax.jit, static_argnames=(
    "with_lse", "interpret", "window", "scale", "tile_q", "heads", "name"))
def _paged_call(q, arena_k, arena_v, page_table, starts, counts, *,
                with_lse: bool, interpret: bool, window=None, scale=None,
                qcounts=None, tile_q=None, heads=None, name=None):
    """The ``pallas_call`` of both wrappers below → (out [n, c, h, dv],
    lse [n, c, h] fp32 or None). The kernel's name in a device trace is
    ``name``; None: ``paged_attn_lse`` with the logsumexp output (a split
    step's history: what the benchmark's ``paged_attn_lse*`` readers divide
    the split steps' bytes by), ``paged_attn`` without (the uniform stack's
    decode read). A typed stack's decode read returns the logsumexp too, for
    its sink, and is no history call: it passes ``DECODE_KERNEL``.
    ``qcounts`` [n]: each row's live queries (None: all ``c``);
    ``tile_q``, ``heads``: another small tile than :func:`tile_queries`',
    other KV heads a program than :func:`heads_per_program`'s, for
    ``tools/bench_paged_hist.py``'s sweeps and the tests alone."""
    bs, lanes = arena_k.shape[1:]
    n, c, h, dh = q.shape
    kvh = lanes // dh
    dv = arena_v.shape[-1] // kvh
    if pairs_heads(dh, dv, kvh):
        out, lse = _paged_call(
            _pair_queries(q, kvh), arena_k, arena_v, page_table, starts,
            counts, with_lse=with_lse, interpret=interpret, window=window,
            scale=1.0 / math.sqrt(dh) if scale is None else scale,
            qcounts=qcounts, tile_q=tile_q, heads=heads, name=name)
        return _unpair_outputs(out, kvh), lse
    groups = h // kvh
    mb = page_table.shape[1]
    rows = groups * c
    hp = heads or heads_per_program(rows, kvh, dh, dv, bs, q.dtype.itemsize)
    if qcounts is None:
        qcounts = jnp.full((n,), c, jnp.int32)

    # [n, c, kvh, g, dh] → [n, kvh, c*g, dh], row index = j*groups + g
    qk = q.reshape(n, c, kvh, groups, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(n, kvh, rows, dh)

    def rows_of(width):
        return pl.BlockSpec((1, hp, rows, width),
                            lambda s, hg, pt, st, ct, qc: (s, hg, 0, 0))

    kernel = functools.partial(
        _paged_kernel, block_size=bs, groups=groups, mb=mb,
        tile_q=tile_q or tile_queries(c, groups), with_lse=with_lse,
        window=window,
        scale=1.0 / math.sqrt(dh) if scale is None else scale)
    out_specs = [rows_of(dv)]
    out_shape = [jax.ShapeDtypeStruct((n, kvh, rows, dv), q.dtype)]
    if with_lse:
        out_specs.append(rows_of(1))
        out_shape.append(jax.ShapeDtypeStruct((n, kvh, rows, 1),
                                              jnp.float32))
    out, *lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, kvh // hp),
            in_specs=[
                rows_of(dh),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, bs, hp * dh), arena_k.dtype),
                pltpu.VMEM((2, bs, hp * dv), arena_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name=name or ("paged_attn_lse" if with_lse else "paged_attn"),
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), qcounts.astype(jnp.int32), qk, arena_k,
      arena_v)

    # [n, kvh, c*g, dv] → [n, c, h, dv]
    out = out.reshape(n, kvh, c, groups, dv).transpose(0, 2, 1, 3, 4) \
        .reshape(n, c, h, dv)
    if not with_lse:
        return out, None
    return out, lse[0].reshape(n, kvh, c, groups).transpose(0, 2, 1, 3) \
        .reshape(n, c, h)


def paged_attention(q: jax.Array, arena_k: jax.Array, arena_v: jax.Array,
                    page_table: jax.Array, starts: jax.Array,
                    counts: jax.Array, *, interpret: bool = False
                    ) -> jax.Array:
    """Pallas paged attention. Same contract as :func:`paged_attention_xla`.

    The page table is a scalar-prefetch operand read INSIDE the kernel to
    drive manual double-buffered DMAs from the HBM arena — no HBM gather,
    no per-page grid step. Dead pages (beyond a sequence's context length)
    are skipped by the dynamic in-kernel loop bound.
    """
    return _paged_call(q, arena_k, arena_v, page_table, starts, counts,
                       with_lse=False, interpret=interpret)[0]


def paged_attention_with_lse(q: jax.Array, arena_k: jax.Array,
                             arena_v: jax.Array, page_table: jax.Array,
                             starts: jax.Array, counts: jax.Array, *,
                             interpret: bool = False,
                             window: Optional[int] = None,
                             scale: Optional[float] = None,
                             qcounts: Optional[jax.Array] = None,
                             name: Optional[str] = None):
    """Pallas paged attention returning (out, lse [n, c, h] fp32) for the
    partial-attention merge. ``counts=0`` gives HISTORY-only semantics
    (keys [0, starts)) — a split step's history part, where the arena
    is a read-only input rather than a carried/donated buffer; with the
    step's ``counts`` the rows' own keys are read from the pool too (a typed
    stack's decode read, after its write: ``name=DECODE_KERNEL``).
    K and V may differ in width (q as wide as a K head, the output as
    wide as a V head); ``window``: key j is visible to query i only when
    ``i - j < window`` and the walk starts at the window's first page;
    ``scale``: the scores' factor, default ``dk ** -0.5`` (a caller that
    zero-pads the heads passes the true width's). ``qcounts`` [n]: the
    LIVE queries of each row, the leading ``qcounts[i]`` of its ``c``
    (default: all ``c``); the kernel's work follows them
    (:func:`_paged_kernel`), and a query past them gets zeros and an lse
    of -1e30. ``name``: the kernel's name in a device trace
    (:func:`_paged_call`)."""
    return _paged_call(q, arena_k, arena_v, page_table, starts, counts,
                       with_lse=True, interpret=interpret, window=window,
                       scale=scale, qcounts=qcounts, name=name)


# ---------------------------------------------------------------------------
# Latent pool kernel (DeepSeek-V3 MLA, absorbed form)
# ---------------------------------------------------------------------------

#: queries of one row that share a kernel program (times the heads: the
#: program's matmul rows). A decode row carried in a chunk-wide step has one
#: live query, so only its first tile computes
MLA_TILE_QUERIES = 8
#: pages a loop turn of the kernel takes (one DMA each, into one buffer):
#: a turn's fixed cost — the waits, the loop, the rescale of the accumulator
#: — is paid once for them, and the matmuls are as many times wider. A row
#: whose live pages are not a multiple reads what its page table holds next
#: (a padded entry is the trash page), masked by position. On a v5e, 64
#: decode rows over pages of [128, 640] bf16 (my chip run, PR 35): 0.53 /
#: 0.34 / 0.26 / 0.22 us a page at 1 / 2 / 4 / 8 pages a turn, and 0.07 /
#: 0.09 / 0.12 / 0.19 ms a call beside them: at the cell's 864 live pages a
#: layer 0.53 / 0.39 / 0.34 / 0.38 ms
MLA_PAGES_PER_TURN = 4


def _mla_kernel(pt_ref, starts_ref, kcounts_ref, qcounts_ref, q_ref,
                *rest, block_size: int, heads: int, tile_q: int,
                scale: float, mb: int, v_lanes: int, pages: int,
                picked: bool = False):
    """Grid (n_seq, query tiles): ONE program per sequence and tile of
    ``tile_q`` queries x all heads, walking the sequence's LIVE pages of
    the latent pool ``pages`` a loop turn with double-buffered DMAs, as
    :func:`_paged_kernel` does. A page ``[bs, W]`` is copied ONCE: the
    whole row is the key (the absorbed query is ``W`` wide, zero where the
    pool is padded) and its first ``v_lanes`` lanes are the value, so the
    accumulator stays in the latent space — the heads' ``W_UV`` comes
    after, outside.

    q_ref: [1, rows, W], row = query * heads + head (query-major: the
    caller's ``[n, c, H, W]`` as it lies); visible keys: ``kpos <= start +
    query`` and ``kpos < start + kcounts`` (``kcounts`` 0: the history
    before the chunk). A tile whose first query is not live
    (``>= qcounts``), or a row with no visible page, writes zeros and an
    lse of -1e30 (a weight of 0 in a merge).

    ``picked``: one operand more after ``q_ref``, ``bias_ref`` [1, tile_q,
    positions] float32 — 0 where the tile's query PICKED the key at that
    position, -1e30 where it did not (:func:`topk_mask` of its indexer's
    scores), the same for every head —, added to each turn's scores: the
    softmax runs over the visible keys the query picked and no other. The
    walk still copies every live page (a chunk's 128 queries pick, between
    them, most of a history), so a picked walk costs what the dense one
    does; what reads ONLY the picked rows is :func:`picked_attention`."""
    if picked:
        bias_ref, *rest = rest
    pool_hbm, o_ref, lse_ref, buf, sem = rest
    s_idx = pl.program_id(0)
    tile = pl.program_id(1)
    rows = q_ref.shape[1]
    span = pages * block_size           # key positions a turn covers
    start = starts_ref[s_idx]
    ctx = start + kcounts_ref[s_idx]
    turns = lax.div(jnp.minimum(ctx, mb * block_size) + span - 1,
                    jnp.int32(span))
    live = (tile * tile_q < qcounts_ref[s_idx]) & (turns > 0)

    def copies(turn, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[pt_ref[s_idx, jnp.minimum(turn * pages + i, mb - 1)]],
            buf.at[slot, pl.ds(i * block_size, block_size)],
            sem.at[slot, i]) for i in range(pages)]

    @pl.when(live)
    def _run():
        for copy in copies(0, 0):
            copy.start()
        q = q_ref[0]                                        # [rows, W]
        qpos = start + tile * tile_q + lax.div(
            lax.broadcasted_iota(jnp.int32, (rows, span), 0),
            jnp.int32(heads))

        def body(b, carry):
            acc, m_prev, l_prev = carry
            slot = lax.rem(b, 2)

            @pl.when(b + 1 < turns)
            def _prefetch():
                for copy in copies(b + 1, lax.rem(b + 1, 2)):
                    copy.start()

            for copy in copies(0, slot):
                copy.wait()
            blk = buf[slot]                                 # [span, W]
            s = lax.dot_general(q, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            kpos = b * span + \
                lax.broadcasted_iota(jnp.int32, (rows, span), 1)
            # (a turn's last page may lie past the table's width: the DMA
            # repeated the last entry, and no query sees those positions)
            s = jnp.where((kpos <= qpos) & (kpos < ctx) &
                          (kpos < mb * block_size), s, _NEG_INF)
            if picked:
                # a query's bias, once for each of its heads' rows
                bias = bias_ref[0, :, pl.ds(pl.multiple_of(b * span, 128),
                                            span)]
                s = s + jnp.broadcast_to(
                    bias[:, None, :], (tile_q, heads, span)
                ).reshape(rows, span)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            # float mask arithmetic, as _paged_kernel
            alive = (m_new > _NEG_INF / 2).astype(jnp.float32)
            p = p * alive[:, None]
            corr = jnp.exp(m_prev - m_new) * alive
            acc = acc * corr[:, None] + lax.dot_general(
                p.astype(blk.dtype), blk[:, :v_lanes],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_prev * corr + jnp.sum(p, axis=1)

        acc, m, l = lax.fori_loop(
            0, turns, body,
            (jnp.zeros((rows, v_lanes), jnp.float32),
             jnp.full((rows,), _NEG_INF, jnp.float32),
             jnp.zeros((rows,), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(m > _NEG_INF / 2, m + jnp.log(l),
                               _NEG_INF)[:, None]

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0] = jnp.zeros_like(o_ref[0])
        lse_ref[0] = jnp.full_like(lse_ref[0], _NEG_INF)


@functools.partial(jax.jit, static_argnames=("v_lanes", "scale", "interpret"))
def mla_decode(q: jax.Array, pool: jax.Array, page_table: jax.Array,
               starts: jax.Array, kcounts: jax.Array, qcounts: jax.Array, *,
               v_lanes: int, scale: float, interpret: bool = False,
               picked: Optional[jax.Array] = None):
    """Absorbed latent attention over the paged latent pool (Pallas): q
    [n, c, H, W] (each head's query in the latent space, ``W`` the pool's
    lanes) → (out [n, c, H, v_lanes] — the softmax-weighted sum of the
    rows' first ``v_lanes`` lanes —, lse [n, c, H] float32). Row i's query
    j sees keys ``[0, min(starts[i] + j + 1, starts[i] + kcounts[i]))``:
    ``kcounts = counts`` is the decode step's read of what it has just
    written, ``kcounts = 0`` the split step's history. ``qcounts`` [n]:
    the live queries of each row; the tiles past them are not computed.
    Its name in a device trace is ``mla_decode``. ``picked`` [n, c, mb *
    bs] bool: of the visible keys, those each query's indexer kept — the
    softmax runs over them alone, and the trace's name is
    ``mla_decode_picked``."""
    n, c, h, w = q.shape
    bs = pool.shape[1]
    mb = page_table.shape[1]
    tile_q = next(t for t in (MLA_TILE_QUERIES, 4, 2, 1) if c % t == 0)
    rows = tile_q * h
    pages = min(MLA_PAGES_PER_TURN, mb)
    operands, specs = [q.reshape(n, c * h, w)], []
    if picked is not None:
        span = pages * bs
        reach = -(-mb * bs // span) * span      # what the turns slice
        bias = jnp.where(picked, 0.0, _NEG_INF).astype(jnp.float32)
        operands.append(jnp.pad(bias, ((0, 0), (0, 0),
                                       (0, reach - bias.shape[-1])),
                                constant_values=_NEG_INF))
        specs.append(pl.BlockSpec(
            (1, tile_q, reach),
            lambda s, t, pt, st, kc, qc: (
                s, jnp.where(t * tile_q < qc[s], t, 0), 0)))

    def tile_of(width, skip_dead):
        def index(s, t, pt, st, kc, qc):
            # a dead tile names the block the row's first tile took: the
            # pipeline copies nothing in for it
            return (s, jnp.where(t * tile_q < qc[s], t, 0) if skip_dead
                    else t, 0)
        return pl.BlockSpec((1, rows, width), index)

    out, lse = pl.pallas_call(
        functools.partial(_mla_kernel, block_size=bs, heads=h, tile_q=tile_q,
                          scale=scale, mb=mb, v_lanes=v_lanes, pages=pages,
                          picked=picked is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, c // tile_q),
            in_specs=[tile_of(w, True), *specs,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[tile_of(v_lanes, False), tile_of(1, False)],
            scratch_shapes=[pltpu.VMEM((2, pages * bs, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, pages))],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, c * h, v_lanes), q.dtype),
                   jax.ShapeDtypeStruct((n, c * h, 1), jnp.float32)],
        interpret=interpret,
        name="mla_decode" if picked is None else "mla_decode_picked",
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      kcounts.astype(jnp.int32), qcounts.astype(jnp.int32), *operands, pool)
    return out.reshape(n, c, h, v_lanes), lse.reshape(n, c, h)


def paged_history_with_lse(q: jax.Array, arena_k: jax.Array,
                           arena_v: Optional[jax.Array],
                           page_table: jax.Array, starts: jax.Array,
                           qcounts: jax.Array, *, kernel: bool,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           v_lanes: Optional[int] = None,
                           picked: Optional[jax.Array] = None):
    """A split step's HISTORY reader under one signature → (out, lse [n,
    c, h] float32): row i's queries (the leading ``qcounts[i]`` are live)
    over the keys ``[0, starts[i])`` the pools held before the step.
    ``kernel``: the paged kernels, which walk each row's live pages and
    compute its live queries alone — :func:`paged_attention_with_lse`, or
    :func:`mla_decode` over a latent pool (``v_lanes``; ``arena_v`` None)
    —; else :func:`paged_attention_hist_xla`, which gathers the page
    table's width and computes every query. ``picked`` [n, c, positions]
    bool (a latent pool): the keys each query's indexer kept."""
    if not kernel:
        return paged_attention_hist_xla(q, arena_k, arena_v, page_table,
                                        starts, window=window, scale=scale,
                                        v_lanes=v_lanes, picked=picked)
    none = jnp.zeros_like(starts)
    if v_lanes is not None:
        return mla_decode(q, arena_k, page_table, starts, none, qcounts,
                          v_lanes=v_lanes, scale=scale, picked=picked)
    return paged_attention_with_lse(q, arena_k, arena_v, page_table, starts,
                                    none, window=window, scale=scale,
                                    qcounts=qcounts)


# ---------------------------------------------------------------------------
# Picked keys (DeepSeek-V3.2's indexer over a latent pool)
# ---------------------------------------------------------------------------

#: heads of an indexer whose scores are alive at once where a chunk's
#: queries score a whole page table (:func:`index_scores`): [128 queries, 4
#: heads, 20,992 keys] float32 is 43 MB where all 32 would be 344
INDEX_HEADS_PER_TURN = 4


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """``I[n, t, s] = Σ_j w[n, t, j] · ReLU(q[n, t, j] · k[n, s])``,
    float32: q [n, c, J, d] (an indexer's rotated queries), k [n, S, d]
    (one key a position), w [n, c, J] float32 (the heads' weights, the two
    ``^-0.5`` folded in). Products in q's dtype, sums in float32 — the
    heads' weighted sum elementwise, NOT a matmul: a float32 dot takes the
    chip's default precision, one bf16 pass. One query a row scores its
    heads at once; a chunk's queries walk the rows and, in a row, the heads
    ``INDEX_HEADS_PER_TURN`` at a time, so the scores of all heads are
    never alive together."""
    n, c, J, d = q.shape
    k = k.astype(q.dtype)

    def heads(q, k, w):         # [.., c, j, d], [.., S, d], [.., c, j]
        s = jnp.einsum("...cjd,...sd->...cjs", q, k,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=-2)

    g = INDEX_HEADS_PER_TURN
    if c == 1 or J % g:
        return heads(q, k, w)
    q = jnp.moveaxis(q.reshape(n, c, J // g, g, d), 2, 1)   # [n, J/g, c, ..]
    w = jnp.moveaxis(w.reshape(n, c, J // g, g), 2, 1)

    def row(args):
        q_r, k_r, w_r = args

        def turn(acc, qw):
            return acc + heads(qw[0], k_r, qw[1]), None

        return lax.scan(turn, jnp.zeros((c, k_r.shape[0]), jnp.float32),
                        (q_r, w_r))[0]

    return lax.map(row, (q, k, w))


def index_scores_paged(q: jax.Array, w: jax.Array, pool: jax.Array,
                       page_table: jax.Array) -> jax.Array:
    """:func:`index_scores` of each row's queries against the keys its
    pages of the index pool hold: q [n, c, J, d], w [n, c, J], pool
    [pages, bs, d], page_table [n, mb] (this layer's region) → [n, c, mb *
    bs] float32. EVERY position of the page table's width is scored (a
    padded entry is the trash page); the caller masks what a query cannot
    see."""
    n, mb = page_table.shape
    return index_scores(q, pool[page_table].reshape(n, mb * pool.shape[1],
                                                    -1), w)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 → uint32 keys in the same order (-0.0 under +0.0)."""
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def causal_only(scores: jax.Array) -> jax.Array:
    """A chunk's scores of its OWN keys [..., c, c] with ``-inf`` where the
    key comes after the query: what the selections below take as "not
    visible"."""
    c = scores.shape[-1]
    return jnp.where(jnp.tril(jnp.ones((c, c), bool)), scores, -jnp.inf)


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """[..., S] float32 (``-inf`` where a key is not visible) → bool [...,
    S]: the ``k`` highest visible scores of each row, all of them where
    there are ``k`` or fewer, ties to the LOWER position (what
    ``lax.top_k`` keeps). Exact, and no sort: the k-th highest value is
    found bit by bit — 32 passes that count the scores at or above a
    candidate — and a running count settles the ties at that value, in the
    launches that have any (float32 scores of 20,000 keys seldom do: the
    count is a pass of its own, 0.6 ms at ``[512, 21120]`` on a v5e)."""
    keys = _sortable(scores)
    visible = scores > -jnp.inf
    if scores.shape[-1] <= k:
        return visible
    kk = jnp.uint32(k)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.uint32) >= kk
        return jnp.where(enough, cand, thr)

    thr = lax.fori_loop(0, 32, bit,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = keys > thr
    at = keys == thr
    room = kk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.uint32)
    tied = jnp.sum(at, axis=-1, keepdims=True, dtype=jnp.uint32) > room
    at = lax.cond(
        jnp.any(tied),
        lambda: at & (jnp.cumsum(at, axis=-1, dtype=jnp.uint32) <= room),
        lambda: at)
    return visible & (above | at)


def topk_picks(scores: jax.Array, k: int):
    """[n, S] float32 (``-inf`` where a key is not visible) → (positions
    [n, K] int32, live [n, K] bool), ``K = min(k, S)``: each row's ``K``
    highest scores as POSITIONS, ``live`` where the pick is a visible key
    (a row that sees fewer than ``K`` keys picks them all; the rest of its
    picks are dead)."""
    vals, picks = lax.top_k(scores, min(k, scores.shape[-1]))
    return picks.astype(jnp.int32), vals > -jnp.inf


def picked_attention(q: jax.Array, pool: jax.Array, page_table: jax.Array,
                     picks: jax.Array, live: jax.Array, *, v_lanes: int,
                     scale: float):
    """Absorbed latent attention of rows of ONE query over the rows of the
    latent pool each PICKED, read by token index: q [n, 1, H, W], picks
    [n, K] positions of each row's sequence, live [n, K] → (out [n, 1, H,
    v_lanes], lse [n, 1, H] float32). A position becomes (page, offset)
    through the row's page table and the gather copies ``K`` rows of ``W``
    lanes a query — ``min(context, index_topk)`` of them live — where
    :func:`mla_decode` copies every live page; the softmax is over the live
    picks. A row with none gives an lse of about -1e30."""
    bs = pool.shape[1]
    page = jnp.take_along_axis(
        page_table, jnp.minimum(picks // bs, page_table.shape[1] - 1),
        axis=1)
    rows = pool[page, picks % bs][:, :, None]              # [n, K, 1, W]
    return _masked_attention(q, rows, rows[..., :v_lanes],
                             live[:, None, None, None], True, scale)


def supported(head_dim: int, block_size: int) -> bool:
    """Shape gate for the Pallas path: the KV block's last two dims
    (block_size, head_dim) must satisfy the (8, 128) tile rule, and the
    kernel only pays off on TPU. Query rows (groups × chunk) need no gate —
    Pallas pads the sublane dim."""
    return head_dim % 128 == 0 and block_size % 8 == 0 and \
        jax.default_backend() == "tpu"
