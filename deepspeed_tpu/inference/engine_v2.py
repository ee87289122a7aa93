"""Ragged-batching inference engine — the FastGen-core engine.

Reference: ``InferenceEngineV2`` (deepspeed/inference/v2/engine_v2.py:30) —
``put(batch_uids, batch_tokens)`` runs one forward over a ragged batch,
``query``/``can_schedule`` expose capacity, ``flush`` releases finished
sequences. The reference's paged-KV CUDA kernels (kernels/ragged_ops/) map
to :mod:`deepspeed_tpu.ops.paged_attention`: a Pallas kernel whose KV DMAs
are addressed by a scalar-prefetched page table, plus an XLA gather path
for prefill chunks and non-TPU backends.

Scheduling is Dynamic-SplitFuse style (RaggedScheduler): each engine step
mixes prefill chunks and single-token decodes into one ragged batch, so
decode latency is bounded while prefill throughput stays high. Shapes are
bucketed (batch rows to powers of two, chunk width to {1, prefill_chunk})
so jit traces a handful of programs, not one per batch composition.
"""

import time
from collections import deque
from functools import partial
from typing import (Any, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.config.config_utils import TPUConfigModel
from deepspeed_tpu.inference import launch_work
from deepspeed_tpu.inference.ragged import (DSStateManager, RaggedBatch,
                                            RaggedScheduler)
from deepspeed_tpu.models.transformer import (STATE_SPACE_KINDS,
                                              DecoderConfig, _mlp, _norm,
                                              block_combine,
                                              attn_out_project, embed_tokens,
                                              init_params,
                                              lm_logits, qkv_project,
                                              rope_table)
from deepspeed_tpu.models import typed_layers as tl
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.utils.logging import log_dist


class RaggedInferenceConfig(TPUConfigModel):
    """Reference: inference/v2/config_v2.py (RaggedInferenceEngineConfig)."""
    dtype: str = "bfloat16"
    max_sequences: int = 64          #: concurrent sequences (state slots)
    num_blocks: int = 512            #: KV arena pages
    block_size: int = 128            #: tokens per page
    max_seq_len: int = 4096          #: page-table width = ceil(/block_size)
    #: scheduler token budget per step; also what a chunk step whose rows
    #: hold more slots packs its tokens into (_token_capacities)
    max_batch_tokens: int = 2048
    prefill_chunk: int = 256         #: SplitFuse chunk width
    use_pallas: Optional[bool] = None  #: None = auto (TPU only)
    weight_quant: Optional[str] = None  #: "int8"|"fp8"|"int4"|"fp6" weight-only


class _RowGroup(NamedTuple):
    """Rows of a step as attention sees them (:meth:`_TokenLayout.groups`):
    ``ids`` [m] names the batch's rows (None: all ``n``, in order), each
    ``c`` slots wide with ``counts`` [m] live queries; ``source`` [m, c]
    are the packed slots its row slots read (None: the row form)."""
    ids: Optional[jax.Array]
    c: int
    counts: jax.Array
    source: Optional[jax.Array]

    def of(self, per_row: jax.Array) -> jax.Array:
        """A per-row operand (``starts``, the page table) of these rows."""
        return per_row if self.ids is None else per_row[self.ids]

    def take(self, x: jax.Array) -> jax.Array:
        """The token-wise form → these rows ``[m, c, ...]``: a gather of
        packed slots, nothing is scattered. The slots a row does not fill
        hold what follows it — the next rows' tokens — as a row-form
        step's hold the activations of token id 0: finite, and nothing
        reads them (a chunk's attention is causal, so a live query sees
        live keys alone; the KV write takes the packed tokens)."""
        return x if self.source is None else x[0][self.source]


class _TokenLayout:
    """Where a ragged batch's tokens sit for whatever acts on a token
    alone (embedding, norms, projections, RoPE, MLP / MoE, residual adds,
    the KV write): in ROWS ``[n, c, ...]`` as they arrive (``capacity``
    None), or PACKED ``[1, capacity, ...]``, row after row with no padding
    between, so that those work on ``capacity`` slots and not on
    ``n * c``. Row ``r``'s tokens are the packed slots ``offsets[r] ..
    offsets[r] + counts[r]``; ``capacity`` (STATIC) must hold
    ``counts.sum()``, the caller's promise. ``row``, ``positions``
    (``starts[row]`` + the slot's column in its row) and ``valid`` say of
    each slot, in the token-wise form, whose token it is, which one, and
    whether it holds a token at all.

    Attention works on rows, and a split step's on ROW GROUPS
    (:meth:`groups`, :func:`_split_attention`). ``chunk_rows`` (STATIC,
    ``P``; None or ``n``: every row) is how many rows a packed step gives
    the chunk's width: with ``P < n`` the rows that hold MORE THAN ONE
    token — at most ``P``, the caller's promise — are gathered into a
    chunk group ``[P, c, ...]`` and every row is also read as a row of ONE
    query, its first packed token (``[n, 1, ...]``); a packed slot then
    takes its row's chunk-group result if its row is in that group, else
    its row's one-token result (:meth:`from_groups`). What still keeps
    the row form of all ``n`` rows at the chunk's width (:meth:`to_rows`):
    the fresh and paged modes' attention, a split step in the row form,
    and a packed split step at ``P = n``."""

    def __init__(self, counts: jax.Array, starts: jax.Array, c: int,
                 capacity: Optional[int], chunk_rows: Optional[int] = None):
        self.n, self.c = counts.shape[0], c
        self.counts, self.capacity = counts, capacity
        self.chunk_rows = None
        if capacity is None:
            self.row, self.positions, self.valid = pa.row_slots(
                starts, counts, c)
            return
        ends = jnp.cumsum(counts)
        self.offsets = ends - counts
        t = jnp.arange(capacity, dtype=jnp.int32)
        # slot t belongs to the first row that ends after it (rows with
        # no token are passed over); a slot past the batch's tokens reads
        # the last row's tail, is never read back, and is NOT valid: its
        # clipped row / col are a live token's, and only ``valid`` keeps
        # the KV write from landing there
        row = jnp.minimum(jnp.sum(t[:, None] >= ends[None], axis=1,
                                  dtype=jnp.int32), self.n - 1)
        col = jnp.minimum(t - self.offsets[row], c - 1)
        self.row = row[None]
        self.slot = row * c + col           # [capacity] into [n * c]
        self.source = jnp.minimum(          # [n, c] into [capacity]
            self.offsets[:, None] + jnp.arange(c, dtype=jnp.int32)[None],
            capacity - 1)
        self.positions = (starts[row] + col)[None]
        self.valid = (t < ends[-1])[None]
        if chunk_rows is not None and chunk_rows < self.n:
            self.chunk_rows = chunk_rows
            wide = counts > 1
            place = jnp.cumsum(wide) - 1    # a wide row's place in the group
            p = jnp.arange(chunk_rows, dtype=jnp.int32)
            # the group's p-th row: the wide row whose place is p; past the
            # last one, row 0 riding along with no live query
            self.chunk_ids = jnp.sum(
                jnp.where(wide[None] & (place[None] == p[:, None]),
                          jnp.arange(self.n, dtype=jnp.int32)[None], 0),
                axis=1, dtype=jnp.int32)
            self.chunk_counts = jnp.where(p <= place[-1],
                                          counts[self.chunk_ids], 0)
            self.in_chunk = wide[row]       # [capacity]
            self.chunk_slot = jnp.minimum(place[row], chunk_rows - 1) * c \
                + col                       # [capacity] into [P * c]

    def kv_slots(self):
        """``(row, pos, valid)`` as ``pa.write_kv`` / ``pa.write_rows``
        take them, each ``[slots]``: one update a slot of the token-wise
        form."""
        return tuple(a.reshape(-1)
                     for a in (self.row, self.positions, self.valid))

    def to_tokens(self, rows: jax.Array) -> jax.Array:
        """[n, c, ...] → the token-wise form (a gather of tokens)."""
        if self.capacity is None:
            return rows
        return rows.reshape((self.n * self.c,) + rows.shape[2:])[
            self.slot][None]

    def to_rows(self, x: jax.Array) -> jax.Array:
        """The token-wise form → [n, c, ...]: row r's slot j reads packed
        slot ``offsets[r] + j`` (:meth:`_RowGroup.take` of every row)."""
        return x if self.capacity is None else x[0][self.source]

    def groups(self) -> Tuple[_RowGroup, ...]:
        """The row groups a split step's attention works on: every row at
        the chunk's width; or (``chunk_rows``) the chunk group, then all
        ``n`` rows as rows of one query — a row of the chunk group or
        with no token rides along there with no live query, its result
        never read."""
        if self.chunk_rows is None:
            return (_RowGroup(None, self.c, self.counts, None if
                              self.capacity is None else self.source),)
        first = jnp.minimum(self.offsets, self.capacity - 1)[:, None]
        return (_RowGroup(self.chunk_ids, self.c, self.chunk_counts,
                          self.source[self.chunk_ids]),
                _RowGroup(None, 1, (self.counts == 1).astype(jnp.int32),
                          first))

    def from_groups(self, parts) -> jax.Array:
        """The groups' results (``[m, c, ...]`` each, as :meth:`groups`
        orders them) → the token-wise form: two gathers and a select over
        the packed slots where there are two groups."""
        if len(parts) == 1:
            return self.to_tokens(parts[0])
        chunk, one = parts
        chunk = chunk.reshape((-1,) + chunk.shape[2:])[self.chunk_slot]
        one = one[:, 0][self.row[0]]
        pick = self.in_chunk.reshape((-1,) + (1,) * (chunk.ndim - 1))
        return jnp.where(pick, chunk, one)[None]

    def last(self, x: jax.Array) -> jax.Array:
        """[n, 1, D]: each row's last token (a row without one: garbage)."""
        if self.capacity is None:
            last = jnp.maximum(self.counts - 1, 0)
            return jnp.take_along_axis(x, last[:, None, None], axis=1)
        return x[0][jnp.maximum(self.offsets + self.counts - 1, 0)][:, None]


def _instances(capacities, n: int, c: int) -> Tuple[Tuple[int, int], ...]:
    """``(capacity, chunk_rows)`` of each instance of a packed step's layer
    loop (:func:`_at_capacity`), one a rung of ``capacities``: the top
    capacity keeps every row a chunk row (``n``), a smaller one gives the
    chunk's width to as many rows as its slots hold whole chunks (of the 64
    x 128 program's rows, 4 at 512 slots and 8 at 1,024) and reads the
    others as rows of one query."""
    return tuple((cap, n if cap == capacities[-1] else max(1, cap // c))
                 for cap in capacities)


def _instance_index(instances, tokens, chunk_rows):
    """Which of ``instances`` (ascending) a batch takes: the first whose
    slots hold its ``tokens`` AND whose chunk group holds its
    ``chunk_rows`` (rows of more than one token). One rule for the
    program (traced scalars) and for the host's accounting (ints)."""
    return sum(1 * ((tokens > cap) | (chunk_rows > rows))
               for cap, rows in instances[:-1])


def _at_capacity(instances, counts: jax.Array, run, *carried):
    """``run(capacity, chunk_rows, *carried)`` at the first of the STATIC
    ``instances`` (:func:`_instances`) that holds the batch
    (:func:`_instance_index`): one branch each of a ``lax.switch`` inside
    the ONE program, so what a batch holds picks the work and no program
    key is added. ``run``'s outputs have the same shapes at every
    instance. ``carried`` (a recurrent stack's state pools): what the
    instance UPDATES and hands back as its LAST output. A conditional does
    not pass a pool through in place — the compiler copies it out of the
    branch and again into the donated buffer, 1.6 GB each at the hybrid
    cell's widths (tests/test_tpu_compile.py) — where a loop's carry does:
    with ``carried``, each instance is the body of a loop of ONE trip if it
    is the batch's and none if not, the pools and the outputs its carry.
    No instance: the row form."""
    if len(instances) <= 1:
        return run(*(instances[0] if instances else (None, None)), *carried)
    index = _instance_index(instances, counts.sum(), (counts > 1).sum())
    if not carried:
        return lax.switch(index,
                          [partial(run, *inst) for inst in instances])
    # the loops' first carry needs the outputs' shapes: the first instance
    # is traced ONCE, to a jaxpr that gives them and that its own loop then
    # replays (an ``eval_shape`` beside the loops was one trace more of
    # every layer, a second of warm set-up at 26 unrolled layers)
    first, shapes = jax.make_jaxpr(partial(run, *instances[0]),
                                   return_shape=True)(*carried)

    def replay(*carried):
        return jax.tree.unflatten(
            jax.tree.structure(shapes), jax.core.eval_jaxpr(
                first.jaxpr, first.consts, *jax.tree.leaves(carried)))

    outs = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes[:-1])
    for k, inst in enumerate(instances):
        def trip(_, carry, fn=partial(run, *inst) if k else replay):
            *outs, state = fn(*carry[0])
            return (state,), tuple(outs)

        carried, outs = lax.fori_loop(
            0, (index == k).astype(jnp.int32), trip, (carried, outs))
    return (*outs, *carried)


def _slot_major(t: jax.Array, slots: int) -> jax.Array:
    """A token-wise tensor (``[1, T, ...]`` packed, ``[n, c, ...]`` rows)
    → ``[slots, lanes]``, slot after slot with a token's values side by
    side as a pool holds them, zero slots after the last: how a split
    step's chunk K/V wait for the write-back, one shape at every
    capacity."""
    t = t.reshape(t.shape[0] * t.shape[1], -1)
    return jnp.pad(t, ((0, slots - t.shape[0]), (0, 0)))


def _write_back_slots(capacities, row_slots: int) -> Tuple[int, int]:
    """``(block, held)`` of a split step's write-back (:func:`_write_back`):
    the slots ONE scatter updates — the smallest capacity, so the lowest
    rung of a ladder is also the write's grain (512 of the 64 x 128
    program's ``(512, 1024, 2048)``: a step of 370 tokens writes one
    block); the row form's ``row_slots`` where the step does not pack —
    and the slots a layer's chunk K/V are held in until then: the top
    capacity in whole blocks."""
    if not capacities:
        return row_slots, row_slots
    return capacities[0], -(-capacities[-1] // capacities[0]) * capacities[0]


def _write_back(counts: jax.Array, starts: jax.Array, c: int, capacities,
                pools, write_layers):
    """A split step's chunk K/V of every layer into ``pools``, after the
    layer loop: block after block of :func:`_write_back_slots` slots until
    the batch's tokens are written, so a launch's scatters perform the
    updates of whole blocks up to the step's tokens (512 or 1,024 for most
    steps of a program that also holds 2,048) and none for slots past
    them. The pools
    are the carry of ONE loop whose trip count the token count sets: they
    alias through it, where a second ``lax.switch`` over write-backs of
    each capacity copies a pool in every branch's layer loop
    (tests/test_tpu_compile.py). ``write_layers(pools, slots, take)``
    writes one block of every layer: ``slots`` are the block's
    ``kv_slots()``, ``take`` cuts its ``[block, lanes]`` out of a layer's
    held ``[held, lanes]``."""
    block, held = _write_back_slots(capacities, counts.shape[0] * c)
    slots = _TokenLayout(counts, starts, c,
                         held if capacities else None).kv_slots()

    def write_block(b, pools):
        def take(t):
            return lax.dynamic_slice_in_dim(t, b * block, block, axis=0)
        return write_layers(pools, tuple(take(a) for a in slots), take)

    # the loop itself carries the word, so what the compiler adds FOR it
    # is its consumer's cost in the scope table, not "(no scope)"
    with jax.named_scope("kv_write"):
        return lax.fori_loop(0, -(-counts.sum() // block), write_block,
                             pools)


def _split_attention(lay: _TokenLayout, qkv, history, own, *,
                     scale: Optional[float] = None, sink=None,
                     q_history=None, expand=None, picked=None) -> jax.Array:
    """A split step's attention of ONE layer, token-wise form in and out:
    for each of the layout's row groups (:meth:`_TokenLayout.groups`)
    unpack ``qkv`` to its rows, read the rows' history — ``history(q,
    group)`` → (out, lse) over the PRE-write pool; ``q_history``: another
    query than ``qkv``'s for it (a latent layer's absorbed one), ``expand``
    its result into the heads' space —, attend the rows' own chunk —
    ``own(q, k, v)`` → (out, lse), causal; a group of one-query rows calls
    nothing: its one key is its own (:func:`pa.one_key_attention_with_lse`)
    —, merge the two partials by their logsumexps in float32 (``sink``: a
    learned logit beside them), and pack the groups' results back. The
    layers differ in the reader and the chunk attention they pass; the
    scopes are the same for all. ``picked(group)`` (a stack that picks its
    keys): which of the chunk's OWN keys each query kept — ``[m, c, c]``
    bool, handed to ``own(q, k, v, picked=)``; for a group of one-query
    rows ``[m]``: whether the row kept its own key (one that did not
    leaves its history alone in the merge)."""
    q_dtype = qkv[0].dtype
    groups = lay.groups()

    def merged(out_h, lse_h, out_c, lse_c):
        if expand is not None:
            out_h = expand(out_h)
        with jax.named_scope("attn_merge"):
            return pa.merge_attention(out_h, lse_h, out_c, lse_c,
                                      sink).astype(q_dtype)

    # every row at the chunk's width and a history to expand: the partials
    # are packed FIRST, so that ``expand`` and the merge run over the
    # packed slots (2,048 for the rows' 8,192; in rows the latent stack's
    # 64-row program holds 0.37 GB more temporaries at two layers)
    pack_first = expand is not None and len(groups) == 1
    outs = []
    for group in groups:
        with jax.named_scope("attn_qkv"):     # attention sees rows
            q, k, v = (group.take(t) for t in qkv)
            q_h = q if q_history is None else group.take(q_history)
        # fresh rows mixed in have an empty history (lse ≈ -1e30 → weight
        # 0); a row with no live query in a group gets the same
        with jax.named_scope("attn_history"):
            out_h, lse_h = history(q_h, group)
        with jax.named_scope("attn_core"):
            if group.c > 1:
                out_c, lse_c = own(q, k, v) if picked is None else \
                    own(q, k, v, picked=picked(group))
            else:
                out_c, lse_c = pa.one_key_attention_with_lse(q, k, v, scale)
                if picked is not None:
                    lse_c = jnp.where(picked(group)[:, None, None], lse_c,
                                      pa._NEG_INF)
        partials = (out_h, lse_h, out_c, lse_c)
        outs.append(partials if pack_first else merged(*partials))
    with jax.named_scope("attn_out"):         # ... and tokens again
        if not pack_first:
            return lay.from_groups(outs)
        partials = [lay.to_tokens(t) for t in outs[0]]
    return merged(*partials)


def ragged_forward(cfg: DecoderConfig, params, arena, tokens: jax.Array,
                   counts: jax.Array, starts: jax.Array,
                   page_table: jax.Array, use_pallas: bool = False,
                   moe_fn=None,
                   fresh_prefill: Union[bool, str] = False,
                   token_capacities: Tuple[int, ...] = (),
                   slots: Optional[jax.Array] = None):
    """One forward over a ragged batch against the paged KV arena.

    tokens: [n, c] (row i valid for j < counts[i]); starts: [n] tokens
    already cached; page_table: [n, mb]. Returns (last-token logits [n, V]
    fp32, updated arena). Rows with counts == 0 produce garbage logits the
    caller ignores. ``slots`` [n] (a recurrent stack only,
    ``cfg.recurrent``): each row's slot of the state pools, padding rows
    the pools' trash slot.

    ``fresh_prefill`` (STATIC): False → every chunk attends through the
    paged arena (the original path). "fresh" → promise that every row
    has starts == 0: attention runs causally WITHIN the chunk and never
    reads the arena. "split" → history attends the PRE-write arena and
    the within-chunk causal part is merged by logsumexp. Both variants
    remove the per-layer write→read dependency on the ~GB arena, which
    XLA otherwise serializes.

    ``token_capacities`` (STATIC, ascending, none over ``n * c``; the
    caller promises ``counts.sum()`` never exceeds the last): a step that
    carries a prompt chunk gives EVERY row the chunk's width, and most
    rows are decode rows with one live token, so ``n * c`` slots hold a
    few hundred tokens. Given capacities, the sublayers that act on a
    token alone — embedding, RoPE tables, norms, the QKV and output
    projections, MLP / MoE, the residual adds, the final norm — run over
    the batch's tokens PACKED into ``[1, capacity, hidden]``
    (:class:`_TokenLayout`); attention works on rows, so q, k, v are
    unpacked for it and its result is packed back before the output
    projection; the KV write takes ``k, v`` as they left the projections,
    one scatter update a packed slot. The head projects each row's last
    packed token. With several capacities the "split" program holds one
    instance of the layer loop for each (:func:`_instances`) and what the
    batch holds picks the first that holds it (:func:`_at_capacity`): the
    arena is a read-only operand of the branches and the write-back
    (:func:`_write_back`) stays outside them. The other modes carry the
    arena through the loop and take ONE capacity. ``()``: the row form
    throughout, which is also what ``c == 1`` always is.

    What attention's rows are. The fresh and paged modes, a split step in
    the row form and the TOP instance of a packed split step unpack all
    ``n`` rows at the chunk's width, ``[n, c, heads, d]``. A SMALLER
    instance of a packed split step works on two row groups
    (:func:`_split_attention`): the at most ``P = capacity // c`` rows
    that hold more than one token, gathered into ``[P, c, ...]`` and
    attended exactly as the row form attends them; and all ``n`` rows as
    rows of ONE query ``[n, 1, ...]`` — the same history reader at
    ``c = 1``, no attention call for the row's own key (``out = v``,
    ``lse = scale * q.k``), the same merge. A batch takes that instance
    iff its tokens fit the capacity AND its rows of more than one token
    fit ``P``; any other batch takes the top instance, where every row is
    a chunk row (``P = n``).

    In the "split" program (``c > 1``) the arena is READ-ONLY during the
    layer loop: the scan carries ``x`` alone, each layer emits its chunk's
    ``k, v`` as scan outputs (token-wise, [L, slots, kvh * dh]) and ONE
    write-back, with the arena as its only carry, scatters them. Nothing in a
    split step reads what the same step wrote, so no layer's reader waits
    for an earlier layer's scatter, and the capacity branches take the
    arena as an operand and return none. The decode program (``c == 1``)
    reads what it has just written, and carries the arena through its
    layer scan: scatter and kernel work on the one token-major layout, so
    the carry aliases (no arena-shaped copy in any step program:
    docs/kernels.md, tests/test_tpu_compile.py).
    The history reader follows ``use_pallas``: the paged kernel
    (:func:`paged_attention_with_lse`, ``counts = 0``, ``qcounts`` = the
    group's live queries: a decode row riding along at the chunk's width
    computes one small tile of its query block) walks only each row's
    ``ceil(start / block_size)`` live pages; the XLA gather
    (:func:`paged_attention_hist_xla`, CPU or a head size the kernel
    refuses) reads the page table's whole width.
    """
    n, c = tokens.shape
    split = fresh_prefill == "split" and c > 1
    token_capacities = tuple(token_capacities)
    if any(cap > n * c for cap in token_capacities) or \
            (len(token_capacities) > 1 and not split):
        raise ValueError(
            f"token_capacities {token_capacities} for a [{n}, {c}] batch: "
            f"none may pass its {n * c} row slots, and only a split step "
            f"takes more than one")
    if cfg.typed:
        return _ragged_forward_typed(cfg, params, arena, tokens, counts,
                                     starts, page_table, use_pallas,
                                     moe_fn, fresh_prefill, token_capacities,
                                     slots)
    if cfg.pos_emb == "alibi":
        # the paged kernels have no score-bias port; serving BLOOM-class
        # models needs the v1 cached engine (forward_with_cache applies
        # alibi internally)
        raise NotImplementedError(
            "ragged/paged inference does not support ALiBi models; use "
            "InferenceEngineTPU (v1 KV-cache path) for BLOOM-class models")
    attend = pa.paged_attention if use_pallas else pa.paged_attention_xla
    if use_pallas:
        from deepspeed_tpu.ops.flash_attention import (
            flash_attention as causal, flash_attention_with_lse)
        own_chunk = partial(flash_attention_with_lse, causal=True)
    else:
        from deepspeed_tpu.models.transformer import \
            dot_product_attention as causal
        own_chunk = pa.causal_attention_with_lse
    # per-layer page stride in the FLAT block pool (init_arena docstring:
    # the pool is a scan CARRY so decode updates it in place; a stacked
    # per-layer arena would be copied wholesale every step)
    num_layers = cfg.num_layers
    stride = arena["k"].shape[0] // num_layers          # num_blocks + 1
    layers = (params["layers"], jnp.arange(num_layers, dtype=jnp.int32))

    held_slots = _write_back_slots(token_capacities, n * c)[1]

    def run(capacity, chunk_rows):
        """Embedding to final norm at one instance → (each row's last
        hidden state [n, 1, D]; split: the chunk's (k, v) of every layer,
        [L, held_slots, kvh * dh], else the written arena's (k, v))."""
        with jax.named_scope("embed"):     # where each token sits, too
            lay = _TokenLayout(counts, starts, c, capacity, chunk_rows)
            toks = lay.to_tokens(tokens)
        positions = lay.positions
        if cfg.pos_emb == "learned":
            emb_pos = jnp.minimum(positions,
                                  params["embed"]["pos"].shape[0] - 1)
        else:
            emb_pos = positions
        x = embed_tokens(cfg, params["embed"], toks, emb_pos,
                         params.get("embed_norm"))
        if cfg.pos_emb == "rope":
            sin, cos = rope_table(cfg, positions)
        else:
            sin = cos = jnp.zeros(positions.shape + (0,), x.dtype)

        def body(carry, layer):
            # split: the arena is a read-only input of the loop (docstring)
            x, ak, av = (carry, arena["k"], arena["v"]) if split else carry
            lp, l_idx = layer
            off = l_idx * stride
            pt_l = page_table + off   # padded entries → this layer's trash
            h_in = _norm(cfg, lp["ln1"], x)
            q, k, v = qkv_project(cfg, lp["attn"], h_in, sin, cos)
            new_kv = (k, v)           # the write takes them token-wise
            if split:
                # continuation / SplitFuse-mixed chunk: the history part
                # reads the PRE-write arena
                def history(q, rows):
                    return pa.paged_history_with_lse(
                        q, ak, av, rows.of(pt_l), rows.of(starts),
                        rows.counts, kernel=use_pallas)

                out = _split_attention(lay, (q, k, v), history, own_chunk)
            else:
                with jax.named_scope("kv_write"):
                    ak, av = pa.write_kv(ak, av, *new_kv, pt_l,
                                         *lay.kv_slots(),
                                         trash_block=off + stride - 1)
                with jax.named_scope("attn_qkv"):     # attention sees rows
                    q, k, v = (lay.to_rows(a) for a in (q, k, v))
                with jax.named_scope("attn_core"):
                    if fresh_prefill == "fresh":
                        # starts == 0 everywhere: the chunk IS the whole
                        # history — plain causal attention over it;
                        # padded-tail rows produce garbage outputs nothing
                        # reads (their KV went to trash)
                        out = causal(q, k, v, causal=True)
                    else:
                        out = attend(q, ak, av, pt_l, starts, counts)
                with jax.named_scope("attn_out"):     # ... and tokens again
                    out = lay.to_tokens(out)
            attn_out = attn_out_project(cfg, lp["attn"], out)
            h_out, _aux = block_combine(cfg, lp, x, h_in, attn_out, moe_fn)
            if split:
                with jax.named_scope("kv_write"):
                    return h_out, tuple(
                        _slot_major(t.astype(pool.dtype), held_slots)
                        for t, pool in zip(new_kv, (ak, av)))
            return (h_out, ak, av), None

        if split:
            x, kv = lax.scan(body, x, layers)
        else:
            (x, *kv), _ = lax.scan(body, (x, arena["k"], arena["v"]),
                                   layers)
        x = _norm(cfg, params["final_norm"], x)
        with jax.named_scope("lm_head"):       # the rows the head projects
            return lay.last(x), tuple(kv)

    x_last, (ak, av) = _at_capacity(_instances(token_capacities, n, c),
                                    counts, run)
    if split:
        def write_layers(pools, slots, take):
            def write(carry, layer_kv):
                k, v, l_idx = layer_kv
                off = l_idx * stride
                with jax.named_scope("kv_write"):
                    return pa.write_kv(*carry, take(k), take(v),
                                       page_table + off, *slots,
                                       trash_block=off + stride - 1), None

            return lax.scan(write, pools, (ak, av, layers[1]))[0]

        ak, av = _write_back(counts, starts, c, token_capacities,
                             (arena["k"], arena["v"]), write_layers)
    logits = lm_logits(cfg, params, x_last)[:, 0]
    return logits, {"k": ak, "v": av}


def _ragged_forward_typed(cfg: DecoderConfig, params, arena,
                          tokens: jax.Array, counts: jax.Array,
                          starts: jax.Array, page_table: jax.Array,
                          use_pallas: bool, moe_fn, fresh_prefill,
                          token_capacities: Tuple[int, ...] = (),
                          slots: Optional[jax.Array] = None):
    """:func:`ragged_forward` for a typed layer stack (models/
    typed_layers.py has the equations): the same three modes over the
    same page table and the same token layouts, the layer loop unrolled
    over the list of layers.

    The arena is a flat dict with a pool per attention kind and per K/V
    (``pa.init_arena_typed``): a layer reads and writes its kind's pools
    at the offset of its index AMONG THE LAYERS OF ITS KIND. A window
    layer keeps its whole history in its pages, and READS only the pages
    its window touches (the XLA forms gather that page range; the paged
    kernel starts its walk there); its learned sink joins the softmax at
    the merge of the history and chunk partials, or over the one softmax
    of a fresh or decode step. Where the K pool is wider than the heads
    (a head of 192 padded to 256 lanes for the kernel) q and k are
    zero-padded to it, and the scores keep the true width's scale.

    On the chip (``use_pallas``) whatever reads the pools is the paged
    kernel, for both kinds: the split step's history (``paged_attn_lse``,
    under ``attn_history``) and the decode step's read of what it has just
    written (the same kernel with the step's ``counts``, one query a row and
    every KV head of the row a program, named ``paged_attn_decode`` under
    ``attn_core``: each row's live pages from its window's first, where the
    XLA form gathers the page table's whole width, or the window's two
    pages, for every row — which a program of few rows over a narrow table
    still does, where that gather is under ``pa.DECODE_KERNEL_BYTES`` a
    layer: tens of microseconds, against a kernel body more to trace and
    lower in every such program of a replica's set-up). The chunk's own
    attention and the fresh step's are the XLA forms (the flash kernels
    take one head width for Q, K and V), and so is the paged mode at ``c >
    1``, the reference the other modes are tested against, which no engine
    selects.

    A LATENT layer (kind 2) has one pool of one row a token (``[c ;
    k_rope]``, zero lanes up to the pool's width) and two forms of one
    attention (``latent_attention`` below): whatever reads the pool — the
    decode step, the split step's history — is ABSORBED (``mla_decode`` on
    the chip, walking each row's live pages; the XLA readers with
    ``v_lanes`` elsewhere), and a chunk's own attention is EXPANDED from
    the chunk's own latents; ``merge_attention`` joins the two partials of
    a split step in the heads' space, on the packed tokens. The step's
    shape picks the form: no option does.

    A latent stack that PICKS ITS KEYS (``cfg.layer_indexer``;
    ``pick_keys`` below) runs three steps more in a layer that OWNS an
    indexer: its index keys go into ``pa.INDEX_POOL`` as the latents go
    into theirs (same page table, same write or write-back); its queries
    SCORE every key they can see — the row's pages of that pool, and in a
    split step the chunk's own index keys beside them —; and the exact
    top ``index_topk`` of those scores are what the layer's softmax runs
    over. The picks are CARRIED through the layer loop (``sel``): a layer
    that borrows reads them as the owner below left them. Their form
    follows the row group: a row of ONE query holds ``index_topk``
    POSITIONS and reads those rows of the latent pool by token index
    (``pa.picked_attention``); a chunk's queries hold a MASK over the page
    table's positions and over their own chunk, under which the history
    walk (``mla_decode``) and the chunk's own attention run — picks fall on
    both sides of the chunk's edge, and ``merge_attention`` joins them as
    it joins the dense partials. With every context at most ``index_topk``
    every key is picked and the result is the dense stack's.

    A STATE-SPACE layer (kinds 3, 4; a gated short convolution, kind 5, is
    one whose rows carry their convolution's tail and NO state: its scan
    steps below fall away) has no pages: what its rows carry lives in
    the state pools (``ops/ssm.init_state_pools``), a slot a sequence
    (``slots``), read and written by every launch that holds the row, in
    every mode; a row at position 0 starts from zero whatever its slot
    held. The layout's row groups pick the FORM of its scan as they pick
    attention's (``state_space`` below): a row of the chunk's width takes
    the chunk form from its carried state, a row of one query the
    recurrence. The pools are carried THROUGH the capacity switch
    (:func:`_at_capacity`), each instance updating them in place: the new
    states of 64 rows are as large as the pool, so they cannot wait for the
    loop's end as a chunk's K/V do. A layer with no mixer (kind -1) is its
    feed-forward part on ``ln1``'s output; a layer with no feed-forward
    part ends at its mixer (``tl.block_residual``)."""
    if cfg.recurrent and slots is None:
        raise ValueError("a recurrent stack (state-space layers) needs each "
                         "row's slot of the state pools: slots=[n]")
    c = tokens.shape[1]
    split = fresh_prefill == "split" and c > 1
    scale = cfg.attn_scale
    of_kind = {a: sum(1 for b in cfg.layer_kinds if b == a)
               for a in set(cfg.layer_kinds)}
    seen = dict.fromkeys(of_kind, 0)
    places = []     # a layer's pools, and where its pages lie in them
    for kind in cfg.layer_kinds:
        if kind in STATE_SPACE_KINDS:   # the layer's own two pools
            places.append(ssm.pool_names(seen[kind]))
            seen[kind] += 1
            continue
        if kind not in pa.KIND_POOLS:
            places.append(None)
            continue
        names = pa.KIND_POOLS[kind]
        stride = arena[names[0]].shape[0] // of_kind[kind]  # num_blocks + 1
        off = seen[kind] * stride
        seen[kind] += 1
        # padded entries of the page table → this layer's trash
        places.append((names, page_table + off, off + stride - 1))

    # an owner's region of the index pool, as ``places`` holds a layer's
    index_places, owners = [], 0
    for l in range(len(cfg.layer_kinds)):
        if not cfg.layer_owns_indexer(l):
            index_places.append(None)
            continue
        stride = arena[pa.INDEX_POOL].shape[0] // cfg.indexer_layers
        off = owners * stride
        owners += 1
        index_places.append(((pa.INDEX_POOL,), page_table + off,
                             off + stride - 1))

    held_slots = _write_back_slots(token_capacities,
                                   tokens.shape[0] * c)[1]

    def write(pools, place, slots, *kv, scope="kv_write"):
        """A layer's chunk into its pools, token-wise (``slots``: the
        layout's ``kv_slots()``): (k, v), or a latent layer's one row a
        token (an indexer's one key: under its own ``scope``)."""
        names, pt_l, trash = place
        with jax.named_scope(scope):
            if len(names) == 1:
                pools[names[0]] = pa.write_rows(
                    pools[names[0]], *kv, pt_l, *slots, trash_block=trash)
            else:
                pools[names[0]], pools[names[1]] = pa.write_kv(
                    pools[names[0]], pools[names[1]], *kv, pt_l, *slots,
                    trash_block=trash)

    def keep(chunk_kv, pools, names, *kv, scope="kv_write"):
        """A split step's chunk of one layer, as it waits for the
        write-back."""
        with jax.named_scope(scope):
            chunk_kv.append(tuple(
                _slot_major(t.astype(pools[name].dtype), held_slots)
                for t, name in zip(kv, names)))

    def heads_attention(lay, kind, a, place, h_in, table, pools, chunk_kv):
        """A full or window layer's attention on its normed input
        (token-wise form) → the heads' outputs in the same form."""
        (kname, vname), pt_l, _ = place
        window, sink = cfg.kind_window(kind), a.get("sink")
        q, k, v = tl.typed_qkv(cfg, kind, a, h_in, *table)
        with jax.named_scope("attn_qkv"):
            pad = pools[kname].shape[-1] // k.shape[2] - cfg.head_dim
            if pad:
                q, k = (jnp.pad(t, ((0, 0),) * 3 + ((0, pad),))
                        for t in (q, k))
        if split:
            def history(q, rows):
                return pa.paged_history_with_lse(
                    q, pools[kname], pools[vname], rows.of(pt_l),
                    rows.of(starts), rows.counts, kernel=use_pallas,
                    window=window, scale=scale)

            keep(chunk_kv, pools, place[0], k, v)
            return _split_attention(
                lay, (q, k, v), history,
                partial(pa.causal_attention_with_lse, window=window,
                        scale=scale), scale=scale, sink=sink)
        write(pools, place, lay.kv_slots(), k, v)
        with jax.named_scope("attn_qkv"):     # attention sees rows
            q, k, v = (lay.to_rows(t) for t in (q, k, v))
        with jax.named_scope("attn_core"):
            if fresh_prefill == "fresh":
                out, lse = pa.causal_attention_with_lse(
                    q, k, v, window=window, scale=scale)
            elif use_pallas and c == 1 and pa.decode_reads_by_kernel(
                    q.shape[0], pt_l.shape[1], window, pools[kname].shape[1],
                    sum(pools[name].shape[-1] * pools[name].dtype.itemsize
                        for name in place[0])):
                # the decode step: the row's own key is in the pool by
                # now, and ``counts`` says so
                out, lse = pa.paged_attention_with_lse(
                    q, pools[kname], pools[vname], pt_l, starts, counts,
                    window=window, scale=scale, qcounts=counts,
                    name=pa.DECODE_KERNEL)
            else:
                out, lse = pa.paged_attention_xla(
                    q, pools[kname], pools[vname], pt_l, starts, counts,
                    window=window, scale=scale, with_lse=True)
            out = tl.apply_sink(out, lse, sink)
        with jax.named_scope("attn_out"):     # ... and tokens again
            return lay.to_tokens(out)

    def pick_keys(lay, p, iplace, h_in, c_q, table, pools, chunk_kv):
        """The three steps of a layer that OWNS an indexer, on its normed
        input → the picks as the layer and its borrowers read them. Fresh
        step: ``[n, c, c]`` bool over the chunk's own keys, or None where
        the chunk is no longer than ``index_topk`` (every key is picked).
        Decode step (after the index keys' write): ``(positions [n, K],
        live [n, K])``. Split step, a row group's width → its picks: a
        group of ONE query ``(positions, live — the picks in the history
        —, own [m]: the row kept its own key)``, a chunk group
        ``(history [m, c, positions], own [m, c, c])`` bool. The index
        pool a split step scores is the one BEFORE its write-back, as the
        latent pool its history reads."""
        names, pt_i, _ = iplace
        topk = cfg.index_topk
        q_i, k_i, w_i = tl.index_qkw(cfg, p, h_in, c_q, *table)
        if split:       # (the write-back itself is one loop: kv_write)
            keep(chunk_kv, pools, names, k_i, scope="attn_index")
        else:
            write(pools, iplace, lay.kv_slots(), k_i, scope="attn_index")
        if fresh_prefill == "fresh":
            if c <= topk:       # every key of the chunk is picked
                return None
            with jax.named_scope("attn_index"):
                scores = pa.index_scores(*(lay.to_rows(t)
                                           for t in (q_i, k_i, w_i)))
            with jax.named_scope("attn_select"):
                return pa.topk_mask(pa.causal_only(scores), topk)
        kpos = jnp.arange(pt_i.shape[1] * pools[names[0]].shape[1],
                          dtype=jnp.int32)
        if not split:           # the decode step: its key is in the pool
            with jax.named_scope("attn_index"):
                scores = pa.index_scores_paged(
                    lay.to_rows(q_i), lay.to_rows(w_i), pools[names[0]],
                    pt_i)
            with jax.named_scope("attn_select"):
                return pa.topk_picks(jnp.where(
                    kpos[None] <= starts[:, None], scores[:, 0], -jnp.inf),
                    topk)
        sel = {}
        for group in lay.groups():
            start = group.of(starts)[:, None, None]
            with jax.named_scope("attn_index"):
                q_g, k_g, w_g = (group.take(t) for t in (q_i, k_i, w_i))
                hist = pa.index_scores_paged(q_g, w_g, pools[names[0]],
                                             group.of(pt_i))
                own = pa.index_scores(q_g, k_g, w_g)
            with jax.named_scope("attn_select"):
                if group.c == 1:
                    # the row's own key stands at ITS position, which the
                    # pool does not hold yet
                    scores = jnp.where(
                        kpos < start, hist, jnp.where(kpos == start, own,
                                                      -jnp.inf))[:, 0]
                    picks, live = pa.topk_picks(scores, topk)
                    mine = picks == start[:, 0]
                    sel[1] = (picks, live & ~mine,
                              jnp.any(live & mine, axis=-1))
                    continue
                both = pa.topk_mask(jnp.concatenate([
                    jnp.where(kpos < start, hist, -jnp.inf),
                    pa.causal_only(own)], axis=-1), topk)
                sel[group.c] = (both[..., :kpos.shape[0]],
                                both[..., kpos.shape[0]:])
        return sel

    def latent_attention(lay, _kind, a, place, h_in, table, pools,
                         chunk_kv, indexer=None, sel=None):
        """A latent layer's attention on its normed input (token-wise
        form) → the heads' outputs [.., H, v] in the same form. What READS
        THE CACHE is absorbed (the decode step, the split step's history:
        queries in the latent space against the pool's rows, ``W_UV``
        after); a chunk's OWN attention is expanded from the chunk's own
        latents (short: ``W_kvb`` over its tokens, heads of nope + rope).
        ``indexer`` (its tree, its region of the index pool): the layer
        owns one and picks (``pick_keys``) into ``sel["picks"]``, which a
        stack that picks its keys carries from layer to layer; the softmax
        of every form runs over those picks."""
        (pool,), pt_l, _ = place
        kl = cfg.kv_lora_rank
        q_nope, q_rope, latent = tl.latent_qkv(cfg, a, h_in, *table)
        if indexer is not None:
            sel["picks"] = pick_keys(
                lay, *indexer, h_in, tl.latent_query_latent(cfg, a, h_in),
                table, pools, chunk_kv)
        picks = sel["picks"] if cfg.picks_keys else None
        own = split or fresh_prefill == "fresh"
        if own:
            qkv = tl.latent_expand_kv(cfg, a, q_nope, q_rope, latent)
        if fresh_prefill != "fresh":
            q_lat = tl.latent_absorb_q(cfg, a, q_nope, q_rope,
                                       pools[pool].shape[-1])
        if split:
            def history(q, rows):
                if picks is not None and rows.c == 1:
                    return pa.picked_attention(
                        q, pools[pool], rows.of(pt_l), *picks[1][:2],
                        v_lanes=kl, scale=scale)
                return pa.paged_history_with_lse(
                    q, pools[pool], None, rows.of(pt_l), rows.of(starts),
                    rows.counts, kernel=use_pallas, scale=scale, v_lanes=kl,
                    picked=None if picks is None else picks[rows.c][0])

            keep(chunk_kv, pools, place[0], latent)
            return _split_attention(
                lay, qkv, history,
                partial(pa.causal_attention_with_lse, scale=scale),
                scale=scale, q_history=q_lat,
                expand=partial(tl.latent_expand_out, cfg, a),
                picked=None if picks is None else
                lambda rows: picks[rows.c][-1])
        write(pools, place, lay.kv_slots(), latent)
        with jax.named_scope("attn_qkv"):     # attention sees rows
            q, *kv = (lay.to_rows(t) for t in (qkv if own else (q_lat,)))
        with jax.named_scope("attn_core"):
            if own:
                out = pa.causal_attention_with_lse(q, *kv, scale=scale,
                                                   picked=picks)[0]
            elif picks is not None:
                out = pa.picked_attention(q, pools[pool], pt_l, *picks,
                                          v_lanes=kl, scale=scale)[0]
            elif use_pallas:
                out = pa.mla_decode(q, pools[pool], pt_l, starts, counts,
                                    counts, v_lanes=kl, scale=scale)[0]
            else:
                out = pa.paged_attention_xla(
                    q, pools[pool], None, pt_l, starts, counts,
                    scale=scale, v_lanes=kl)
        with jax.named_scope("attn_out"):
            out = lay.to_tokens(out)
        return out if own else tl.latent_expand_out(cfg, a, out)

    def state_space(lay, kind, p, place, h32, dtype, pools):
        """A state-space layer's mixer (``tl.mixer_forms`` of its ``kind``)
        on its normed input (token-wise form, float32: read in the compute
        ``dtype`` unless the kind says otherwise, ``tl.mixer_input``) → its
        output in the same form; ``pools`` holds the layer's two state
        pools (``place``: their names; a mixer with no scan has the
        convolution's alone), which it reads and writes. Rows of ONE query first, in SLOT order: the
        layer's whole pool takes one elementwise pass (a slot with no live
        row has ``Δ = 0`` and keeps its state; a wide row's is reset or
        left as it is), the rows' inputs scattered to their slots and their
        outputs gathered back, both small. Then the rows of the chunk's
        width: their states gathered, the chunk form, the results
        scattered (a row riding along with no live query in the chunk
        group writes nothing)."""
        sname, cname = place
        forms = tl.mixer_forms(kind, use_pallas)
        h_in = tl.mixer_input(forms, h32, dtype)
        scans = forms.step is not None
        held = (sname, cname) if scans else (cname,)
        state_scope, conv_scope, scan_scope = forms.scopes
        region = pools[cname].shape[0]
        with jax.named_scope(state_scope):
            # a layer's pools are read at ITS turn: free of the stream, the
            # compiler gathers every layer's rows at the program's start,
            # side by side (64 chunk rows x 9 layers of 4 MiB states: 2.3
            # GB of temporaries at Granite 4.0-H's widths)
            *now, h_in = lax.optimization_barrier(
                (*(pools[name] for name in held), h_in))
            pools.update(zip(held, now))
        z, xbc, dt = forms.project(cfg, p, h_in)
        fresh_row = ssm.fresh_rows(starts)
        groups = lay.groups()
        outs = [None] * len(groups)
        for i, group in sorted(enumerate(groups), key=lambda g: g[1].c):
            at, reset = group.of(slots), group.of(fresh_row)
            live = group.counts
            with jax.named_scope(state_scope):
                tail = ssm.tail_rows(cfg, ssm.carried(pools[cname][at],
                                                      reset))
                if scans and group.c > 1:
                    state = ssm.carried(pools[sname][at], reset)
            with jax.named_scope(conv_scope):
                u, tail = ssm.conv_rows(cfg, p, group.take(xbc), tail, live,
                                        forms.conv_dtype, forms.conv_silu)
                dt_g = jax.tree.map(group.take, dt)
            dt_g = forms.inputs(cfg, p, u, dt_g, live)
            if group.c > 1:
                if scans:
                    with jax.named_scope(scan_scope):
                        outs[i], state = forms.chunk(cfg, p, u, dt_g, state,
                                                     live)
                else:
                    outs[i] = u
                with jax.named_scope(state_scope):
                    to = at if group.ids is None else jnp.where(
                        live > 0, at, region)
                    if scans:
                        pools[sname] = pools[sname].at[to].set(state,
                                                               mode="drop")
                    pools[cname] = pools[cname].at[to].set(
                        tail.reshape(tail.shape[0], -1), mode="drop")
                continue
            with jax.named_scope(state_scope):
                pools[cname] = pools[cname].at[at].set(
                    tail.reshape(tail.shape[0], -1))
                if not scans:       # the taps' multiply-adds were the step
                    outs[i] = u
                    continue

                def by_slot(rows):
                    return jnp.zeros((region,) + rows.shape[1:],
                                     rows.dtype).at[at].set(rows)

                u, dt_g, live, reset = jax.tree.map(
                    by_slot, (u, dt_g, live, reset))
            with jax.named_scope(scan_scope):
                # (the reset rides in the decay: ``ssm.carried`` over the
                # pool would be a second pass over it)
                y, pools[sname] = forms.step(cfg, p, u, dt_g, pools[sname],
                                             live, reset)
            with jax.named_scope(state_scope):
                outs[i] = y[at]
        with jax.named_scope(scan_scope):
            y = lay.from_groups(outs)
        return forms.out(cfg, p, y, z)

    carried = tuple(name for name in arena if ssm.is_state_pool(name))

    def run(capacity, chunk_rows, state=None):
        """Embedding to final norm at one instance → (each row's last
        hidden state [n, 1, D]; split: every attention layer's chunk (k,
        v), which wait for the loop's end, and the state pools ``state``
        as the instance leaves them; else the written pools)."""
        with jax.named_scope("embed"):     # where each token sits, too
            lay = _TokenLayout(counts, starts, c, capacity, chunk_rows)
            toks = lay.to_tokens(tokens)
        x, dtype = tl.residual_stream(      # float32, whatever the weights'
            embed_tokens(cfg, params["embed"], toks, lay.positions))
        x = tl.stream_open(cfg, x)          # (one hidden state a token: x)
        tables = tl.rope_tables(cfg, lay.positions)
        pools = dict(arena, **(state or {}))
        chunk_kv = []
        sel = {}        # the picks an owner of an indexer leaves its borrowers
        for kind, lp, place, iplace in zip(cfg.layer_kinds, params["layers"],
                                           places, index_places):
            h, maps = tl.layer_input(cfg, lp, x)
            if kind in STATE_SPACE_KINDS:
                out = state_space(lay, kind, tl.mixer_tree(kind, lp), place,
                                  h, dtype, pools)
            elif kind < 0:
                out = None
            else:
                attend = latent_attention if kind == 2 else heads_attention
                if cfg.picks_keys:
                    attend = partial(attend, sel=sel, indexer=iplace and (
                        lp["indexer"], iplace))
                out = tl.typed_attn_out(cfg, lp["attn"], attend(
                    lay, kind, lp["attn"], place, h.astype(dtype),
                    tables[kind], pools, chunk_kv), h.astype(dtype))
            x = tl.block_residual(cfg, lp, x, h, out, moe_fn, lay.valid,
                                  dtype, maps)
        x = _norm(cfg, params["final_norm"],
                  tl.stream_close(cfg, x)).astype(dtype)
        with jax.named_scope("lm_head"):       # the rows the head projects
            if not split:
                return lay.last(x), pools
            return lay.last(x), chunk_kv, {name: pools[name]
                                           for name in carried}

    x_last, *out = _at_capacity(
        _instances(token_capacities, tokens.shape[0], c), counts, run,
        *(({name: arena[name] for name in carried},) if carried else ()))
    if not split:
        return lm_logits(cfg, params, x_last)[:, 0], out[0]
    chunk_kv, state = out
    # in the order the layers kept their chunks: an owner's index keys,
    # then the layer's own pools
    paged = [pl for kind, place, iplace in zip(cfg.layer_kinds, places,
                                               index_places)
             if kind in pa.KIND_POOLS for pl in (iplace, place) if pl]

    def write_layers(pools, slots, take):
        pools = dict(pools)
        for place, kv in zip(paged, chunk_kv):
            write(pools, place, slots, *(take(t) for t in kv))
        return pools

    pools = _write_back(counts, starts, c, token_capacities,
                        {name: pool for name, pool in arena.items()
                         if name not in carried}, write_layers)
    return lm_logits(cfg, params, x_last)[:, 0], {**pools, **state}


def _paged_reader(model: DecoderConfig, config) -> Tuple[bool, int]:
    """(the history and decode reads take the paged kernels, the K pool's
    lanes a head). A typed stack's K heads are zero-padded to whole
    128-lane tiles for the paged kernel (192 -> 256); the uniform stack's
    are as wide as the kernel takes them, or it is refused. A latent
    stack's pool holds one row a token, padded likewise (576 -> 640: the
    kernel copies whole pages, and a DMA wants whole lane tiles), and what
    the kernel sums is the row's first kv_lora_rank lanes. Heads of HALF a
    tile (64) stay as wide as they are: the kernel reads two KV heads as
    one of 128 lanes (``pa.pairs_heads``), and a padded head would double
    the bytes a token holds and every page read. ``config.use_pallas`` None:
    the kernels wherever the backend and the shapes take them."""
    latent = model.latent
    width = model.latent_dim if latent else model.head_dim
    paired = model.typed and not latent and all(
        pa.pairs_heads(width, model.v_dim, model.kind_kv_heads(kind))
        for kind in set(model.layer_kinds) & set(pa.KIND_POOLS))
    lanes = -(-width // 128) * 128 if model.typed and not paired else width
    if config.use_pallas is not None:
        use_pallas = bool(config.use_pallas)
    elif paired:
        use_pallas = pa.supported(2 * width, config.block_size)
    else:
        use_pallas = pa.supported(lanes, config.block_size) and \
            (model.kv_lora_rank if latent else model.v_dim) % 128 == 0
    return use_pallas, lanes if use_pallas else width


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


#: the arena's one entry that is NO pool: ``[max_sequences + 1]`` int32, the
#: token each sequence SLOT sampled last (the last row: the trash slot of
#: padding rows). Every token-mode step program writes its ``out`` there,
#: so a row's fed-back token need not pass through the host
#: (:meth:`RaggedInferenceEngineTPU.launch`). It rides in the arena because
#: the programs already donate and return that, so their signature and
#: their grid stay as they are; what walks the arena as pools takes
#: :func:`_pools`.
FED_TOKENS = "fed_tokens"
#: a packed token id that says "this row's token is its slot's of
#: ``FED_TOKENS``" (first column of a row only); in ``seq.tokens`` the
#: placeholder of a token that is still on the device
FED_SENTINEL = -1


def _pools(arena: dict) -> dict:
    """The arena's pools: everything but the slot buffer."""
    return {name: a for name, a in arena.items() if name != FED_TOKENS}


class _Launch(NamedTuple):
    """A step program that was launched and not yet collected: its tokens
    (or logits) on the device, its sampling mode, and the rows whose pending
    tokens it exhausted — ``{uid: (row, descriptor, at)}``, ``at`` the index
    in ``descriptor.tokens`` of the placeholder the row was continued by
    (None: not continued)."""
    out: Any
    mode: Any
    emits: Dict[int, Tuple[int, Any, Optional[int]]]


def _dispatch_count(name: str, by: int = 1) -> None:
    """Bump a ``dispatch/*`` counter (lazy import: telemetry pulls in the
    whole diagnostics stack, which must not load at engine-import time)."""
    from deepspeed_tpu.telemetry.registry import registry
    registry.counter(name).inc(by)


def _step_kind(cb: int, fresh) -> str:
    """Which step program a batch runs: ``decode`` (one token a row),
    ``fresh`` / ``split`` (a prefill chunk attending inside the chunk /
    also the pre-write arena) or ``paged`` (a chunk through the single
    paged read: the reference the other two are tested against, which the
    engine itself never selects). The tag of the
    ``serving/dispatch`` span and of ``dispatch/steps.<kind>``, and the
    prefix of the program's name after ``serve_``."""
    if cb == 1:
        return "decode"
    return fresh if fresh in ("fresh", "split") else "paged"


def _mode_suffix(mode) -> str:
    """The statics of a step program's sampling mode as part of its name,
    so that one name stays one compiled module; greedy token ids, the
    serving default, add nothing."""
    if mode is None:
        return "_logits"
    if mode[0] == "argmax":
        return ""
    _, top_k, use_top_p = mode
    return f"_sample_k{top_k}" + ("_p" if use_top_p else "")


@jax.named_scope("sample")
def _sample_tokens(logits, mode, temperature, top_p, rng):
    """Shared on-device sampling (mode is STATIC: ('argmax',) or
    ('sample', top_k, use_top_p); temperature/top_p are traced scalars so
    per-request changes don't recompile)."""
    if mode[0] == "argmax":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
    _, top_k, use_top_p = mode
    lg = logits / temperature
    if top_k > 0:
        kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
        lg = jnp.where(lg < kth, -1e30, lg)
    if use_top_p:
        sorted_lg = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_lg, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_lg, cutoff_idx, axis=-1)
        lg = jnp.where(lg < cutoff, -1e30, lg)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, lg, axis=-1).astype(jnp.int32), rng


def _step_program(model: DecoderConfig, nb: int, cb: int, mb: int, mode,
                  fresh, capacities, use_pallas: bool, moe_fn):
    """The body of a step program (:meth:`RaggedInferenceEngineTPU._step_fn`
    jits it): ``(params, arena, packed, rng) → (out, rng, arena)`` over
    ``nb`` rows of ``cb`` tokens and a page table ``mb`` wide, ``packed``
    as :meth:`RaggedInferenceEngineTPU._pack` lays it out. A row whose
    first packed token is ``FED_SENTINEL`` takes its slot's token of the
    arena's ``FED_TOKENS``; a token mode writes every row's ``out`` there
    by slot. Data, not a trace-time branch: the one program serves a
    caller that feeds tokens back through the host and one that does
    not."""
    def fn(params, arena, packed, rng):
        off = 0
        tokens = packed[off:off + nb * cb].reshape(nb, cb)
        off += nb * cb
        counts = packed[off:off + nb]
        off += nb
        starts = packed[off:off + nb]
        off += nb
        pt = packed[off:off + nb * mb].reshape(nb, mb)
        off += nb * mb
        slots = packed[off + 2:off + 2 + nb]
        fed = arena[FED_TOKENS]
        with jax.named_scope("embed"):
            # a row whose token is still on the device: its slot's
            first = tokens[:, 0]
            tokens = tokens.at[:, 0].set(
                jnp.where(first == FED_SENTINEL, fed[slots], first))
        logits, arena = ragged_forward(
            model, params, _pools(arena), tokens, counts, starts, pt,
            use_pallas=use_pallas, moe_fn=moe_fn, fresh_prefill=fresh,
            token_capacities=capacities,
            slots=slots if model.recurrent else None)
        if mode is None:
            return logits, rng, {**arena, FED_TOKENS: fed}
        temperature = lax.bitcast_convert_type(packed[off], jnp.float32)
        top_p = lax.bitcast_convert_type(packed[off + 1], jnp.float32)
        out, rng = _sample_tokens(logits, mode, temperature, top_p, rng)
        with jax.named_scope("sample"):
            # every row's, by slot (padding rows: the trash slot); a
            # row with tokens still pending writes what nothing reads
            fed = fed.at[slots].set(out)
        return out, rng, {**arena, FED_TOKENS: fed}
    return fn


class RaggedInferenceEngineTPU:
    """Continuous-batching engine over the paged arena (reference
    inference/v2/engine_v2.py:30)."""

    def __init__(self, model: DecoderConfig,
                 config: Union[Dict[str, Any], RaggedInferenceConfig,
                               None] = None,
                 params=None, rng: Optional[jax.Array] = None):
        """Construction is timed by part, always on
        (``telemetry.compile_monitor.setup_part``): the parameters' cast,
        init and placement (``setup/params``), the arena's allocation
        (``setup/arena``), the rest (``setup/engine``). Each times the
        HOST: the arena's ``jnp.zeros`` and a placement are enqueued, and
        nothing here waits for the device."""
        # here and not at import, as the tracer below
        from deepspeed_tpu.telemetry.compile_monitor import (
            compile_monitor, setup_part)
        # the build record and its counters, tracer on or off: a serving
        # process's step programs are named (``_step_fn``)
        compile_monitor.install()
        with setup_part("engine"):
            self._construct(model, config, params, rng, setup_part)

    def _construct(self, model, config, params, rng, setup_part) -> None:
        """``__init__``'s body, inside ``setup/engine``; ``setup_part`` is
        handed in because telemetry is imported at the call, not here."""
        if isinstance(config, dict) or config is None:
            config = RaggedInferenceConfig(**(config or {}))
        if not model.causal or model.layer_window_pattern is not None:
            # the paged kernels are full-causal per layer: encoders have
            # no decode loop at all, and GPT-Neo's local layers would
            # silently attend beyond their window
            raise NotImplementedError(
                "ragged/paged inference supports full-causal decoder "
                "models only (got "
                f"causal={model.causal}, layer_window_pattern="
                f"{model.layer_window_pattern}); use InferenceEngineTPU "
                "for GPT-Neo-class models")
        if model.typed and config.weight_quant:
            raise NotImplementedError(
                "weight_quant on a typed layer stack (DecoderConfig."
                "layer_kinds) is not built: the quantized-tree walkers "
                "read the stacked layer tree")
        if model.sliding_window is not None and not model.typed and \
                config.max_seq_len > model.sliding_window:
            # the paged kernels attend the full page table; beyond the
            # window that silently diverges from the training forward
            raise NotImplementedError(
                f"ragged/paged inference has no sliding-window mask: "
                f"max_seq_len {config.max_seq_len} exceeds sliding_window "
                f"{model.sliding_window}; cap max_seq_len at the window "
                f"or use InferenceEngineTPU (a typed layer stack, "
                f"DecoderConfig.layer_kinds, has the mask per layer)")
        self.model_config = model
        self.config = config
        from deepspeed_tpu.ops.quantized_linear import validate_weight_quant
        validate_weight_quant(config.weight_quant)
        from deepspeed_tpu.parallel.mesh import get_mesh, has_mesh
        if has_mesh() and get_mesh().shape.get("model", 1) > 1:
            # only UNPACKED quantization shards (qmatmul_tp); packed
            # int4/fp6 always run replicated, so they stay legal here.
            # Check the param tree too: pre-quantized dstpu_quantize
            # trees arrive with weight_quant unset.
            from deepspeed_tpu.inference.engine import (
                _has_packed_leaves, _is_quantized_tree)
            unpacked_q = config.weight_quant in ("int8", "fp8") or (
                params is not None and _is_quantized_tree(params)
                and not _has_packed_leaves(params))
            if unpacked_q:
                raise ValueError(
                    "RaggedInferenceEngineTPU is single-shard: int8/fp8 "
                    "quantized linears route through qmatmul_tp, which "
                    "would shard_map over the ambient mesh's model axis "
                    f"(size {get_mesh().shape['model']}). Build a mesh "
                    "with model=1 for the ragged engine, or use "
                    "InferenceEngineTPU for TP serving.")
        self.dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                      "float16": jnp.float16}[config.dtype]
        #: the paged kernels or the XLA readers; width of the K pool (a
        #: latent stack: of its one pool): the heads' (the row's), or the
        #: padded lanes
        self.use_pallas, self.k_width = _paged_reader(model, config)

        self.state = DSStateManager(max_sequences=config.max_sequences,
                                    num_blocks=config.num_blocks,
                                    block_size=config.block_size,
                                    recurrent=model.recurrent)
        self.scheduler = RaggedScheduler(
            self.state, max_batch_tokens=config.max_batch_tokens,
            prefill_chunk=config.prefill_chunk)
        self.mb = -(-config.max_seq_len // config.block_size)

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        with setup_part("params"):
            cast = lambda t: jax.tree.map(
                lambda x: x.astype(self.dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
            from deepspeed_tpu.inference.engine import _is_quantized_tree
            from deepspeed_tpu.ops.quantized_linear import (
                cast_quantized_tree, quantize_param_tree)
            # explicit accelerator target: plain jax.device_put(x) is an
            # IDENTITY for already-placed arrays, so host-built trees would
            # silently stay CPU-resident and stream per step. The target is
            # the caller's ``jax.default_device`` when one is set (a replica
            # per chip), else the first device
            dflt = jax.config.jax_default_device
            dev0 = dflt if isinstance(dflt, jax.Device) else jax.devices()[0]
            if params is None and config.weight_quant:
                # init + quantize on HOST, ship only the quantized tree (same
                # rationale as the v1 engine: int4 llama-8B serves in ~5 GB
                # but would OOM materialized bf16-first on a 16 GB chip).
                # NOTE: random init is kept on jax PRNG for weight parity with
                # the on-device path — slow for 8B-scale demos (single-core
                # threefry); real large models load checkpoints (hf_loader)
                # or pre-quantized trees instead.
                with jax.default_device(jax.local_devices(backend="cpu")[0]):
                    host = quantize_param_tree(cast(init_params(model, rng)),
                                               mode=config.weight_quant)
                self.params = jax.tree.map(
                    lambda v: jax.device_put(v, dev0), host)
            elif params is not None and _is_quantized_tree(params):
                # pre-quantized (bin/dstpu_quantize / host-quantized) tree:
                # dtype policy must not touch scales / fp8 / packed planes
                if config.weight_quant:
                    raise ValueError(
                        "params are already quantized (scale leaves present); "
                        "drop weight_quant from the config")
                self.params = jax.tree.map(
                    lambda v: jax.device_put(v, dev0),
                    cast_quantized_tree(params, self.dtype))
            else:
                # random init as ONE jitted program (like the v1 engine): run
                # eagerly it compiles a program per leaf shape — 27 compiles,
                # 77 s cold for the 1b preset on a v5e
                self.params = cast(params) if params is not None else \
                    jax.jit(lambda r: cast(init_params(model, r)))(rng)
                if config.weight_quant:
                    self.params = quantize_param_tree(self.params,
                                                      mode=config.weight_quant)
        with setup_part("arena"):
            if model.typed:
                # a pool per attention kind and per K/V, one page table
                self.arena = pa.init_arena_typed(
                    model.layer_kinds,
                    {a: model.kind_kv_heads(a)
                     for a in set(model.layer_kinds)},
                    config.num_blocks, config.block_size, self.k_width,
                    model.v_dim, self.dtype,
                    # a stack that picks its keys: the index pool beside them
                    **(dict(index_layers=model.indexer_layers,
                            index_width=model.index_head_dim)
                       if model.picks_keys else {}))
                if model.recurrent:
                    # beside the pages: a float32 state and a convolution tail
                    # a sequence slot and state-space layer; the pool's size
                    # follows max_sequences
                    self.arena.update(ssm.init_state_pools(
                        model, config.max_sequences, self.dtype))
            else:
                self.arena = pa.init_arena(
                    model.num_layers, model.kv_heads, config.num_blocks,
                    config.block_size, model.head_dim, self.dtype)
            self.arena[FED_TOKENS] = jnp.zeros((config.max_sequences + 1,),
                                               jnp.int32)
        moe_fn = None
        if model.typed:
            # routes over every expert, computes the held ones' part
            from deepspeed_tpu.parallel.moe import held_experts_moe_layer
            moe_fn = held_experts_moe_layer
        elif model.num_experts:
            from deepspeed_tpu.parallel.moe import serving_moe_fn
            from deepspeed_tpu.parallel.mesh import get_mesh, has_mesh
            # same EP guard as the v1 engine: an ambient expert axis > 1
            # means capacity dispatch (the ragged engine itself is
            # single-shard, but the ambient mesh drives GSPMD layouts)
            ep = has_mesh() and get_mesh().shape.get("expert", 1) > 1
            moe_fn = serving_moe_fn(model, config.weight_quant,
                                    self.params, ep=ep)
        self._moe_fn = moe_fn
        #: what every launch's accounting reads of this engine
        self._site = launch_work.Site(
            model, config.block_size, self.mb, self.use_pallas,
            self.k_width, jnp.dtype(self.dtype).itemsize)
        #: jit cache keyed on (n_bucket, c_bucket, mode, fresh) — the
        #: fresh=True/False split legitimately doubles prefill-shape
        #: compiles (arena-reading vs within-chunk attention programs).
        #: The step takes
        #: ONE packed int32 vector (tokens|counts|starts|page_table): one
        #: host→device upload per step instead of four small ones
        self._step_fns: Dict[Any, Any] = {}
        #: jit for prefix-cache copy-on-write page duplication
        self._copy_pages_fn = None
        #: kind of the last device program launched (``_step_kind``): the
        #: ``program`` of ``serving/engine_step``
        self.last_program: Optional[str] = None
        #: when the last launch's ``device_get`` returned (``perf_counter``);
        #: None once the scheduler has had nothing to run, so that an idle
        #: server's waiting is not counted as host time (:meth:`_fetch`)
        self._fetch_returned: Optional[float] = None
        #: launches not yet collected, oldest first (:meth:`launch` /
        #: :meth:`collect`): at most the one being collected and the one
        #: made ahead of it
        self._launched: deque = deque()
        # the process-wide span tracer, looked up once and not once a
        # span (here and not at import: telemetry pulls in the whole
        # diagnostics stack, which must not load at engine-import time)
        from deepspeed_tpu.telemetry.tracer import tracer
        self._tracer = tracer
        self._rng_dev = rng          # defaulted to PRNGKey(0) above
        self._temperature = 1.0      # dynamic sampling scalars, packed
        self._top_p = 1.0            # into the step upload
        log_dist(f"ragged engine ready: blocks={config.num_blocks}x"
                 f"{config.block_size} pallas={self.use_pallas} "
                 f"dtype={config.dtype}")

    def _step_fn(self, nb: int, cb: int, mode,
                 fresh: Union[bool, str] = False):
        """mode: None → raw logits; ("argmax",) → greedy token ids;
        ("sample", top_k, use_top_p) → sampled token ids. Token modes
        fetch [n] int32 instead of the [n, V] fp32 logits (8 MB per step
        for a 128k vocab); the sampling rng lives ON DEVICE and is split
        inside the step (no per-step key upload). Temperature/top_p are
        DYNAMIC scalars bitcast into the packed vector, so changing them
        per request does NOT recompile the model forward (only top_k and
        the top-p on/off switch are static). ``fresh``: False, "fresh" or
        "split" as ``ragged_forward``'s ``fresh_prefill``."""
        key = (nb, cb, mode, fresh)
        if key in self._step_fns:
            return self._step_fns[key]
        # jit-cache miss = one XLA compile; attribute it to the bucket
        # shape so a recompile storm names the drifting request shape
        from deepspeed_tpu.telemetry import compile_monitor
        compile_monitor.count_trace(
            "serving/step_fn", detail={"n_bucket": nb, "chunk": cb,
                                       "mode": str(mode), "fresh": fresh})
        mb = self.mb
        model = self.model_config
        capacities = self._token_capacities(nb, cb, fresh)
        # the module's name in a device trace (after ``jit_``): kind and
        # static shape, so one name is one compiled module
        name = f"serve_{_step_kind(cb, fresh)}_r{nb}" + \
            (f"_c{cb}" if cb > 1 else "") + _mode_suffix(mode)

        fn = _step_program(model, nb, cb, mb, mode, fresh, capacities,
                           self.use_pallas, self._moe_fn)
        fn.__name__ = fn.__qualname__ = name
        jitted = jax.jit(fn, donate_argnums=(1,))
        compile_monitor.register_program(name, jitted, (
            self.params, self.arena,
            jax.ShapeDtypeStruct((self._packed_len(nb, cb),), jnp.int32),
            self._rng_dev))
        self._step_fns[key] = jitted
        return jitted

    def cost_records(self, mode=("argmax",), refresh: bool = False):
        """Compile-time cost records for the prefill/decode bucket
        programs (telemetry/explain.py): per-program FLOPs / bytes /
        roofline ``predicted_s``. Lazily computed and cached — the first
        call costs two abstract XLA compiles; the frontend's SLO
        admission reads ``predicted_s`` from here (0.0 when the platform
        has no peak numbers, e.g. CPU)."""
        if refresh or getattr(self, "_cost_records", None) is None:
            from deepspeed_tpu.telemetry.explain import explain_serving
            self._cost_records = explain_serving(self, mode=mode)
        return self._cost_records

    def _page_table(self, uids: List[int], nb: int) -> np.ndarray:
        """[nb, mb] physical page ids; padding rows/entries point at the
        pool's trash sentinel (num_blocks)."""
        pt = np.full((nb, self.mb), self.config.num_blocks, np.int32)
        for i, uid in enumerate(uids):
            blocks = self.state.seqs[uid].blocks
            pt[i, :len(blocks)] = blocks
        return pt

    def _packed_len(self, nb: int, cb: int) -> int:
        """Length of :meth:`_pack`'s vector: tokens | counts | starts |
        page table | the two sampling scalars | the rows' sequence slots."""
        return nb * cb + 3 * nb + nb * self.mb + 2

    def _pack(self, batch: RaggedBatch, nb: int, cb: int) -> np.ndarray:
        n = len(batch.uids)
        tokens = np.zeros((nb, cb), np.int32)
        c = batch.token_ids.shape[1]
        tokens[:n, :c] = batch.token_ids
        counts = np.zeros((nb,), np.int32)
        counts[:n] = batch.token_counts
        starts = np.zeros((nb,), np.int32)
        starts[:n] = batch.start_positions
        pt = self._page_table(batch.uids, nb)
        sampling = np.asarray([self._temperature, self._top_p],
                              np.float32).view(np.int32)
        # each row's slot of the fed-token buffer (and of a recurrent
        # stack's state pools); padding rows: the trash
        slots = np.full((nb,), self.config.max_sequences, np.int32)
        slots[:n] = batch.slots
        return np.concatenate([tokens.ravel(), counts, starts, pt.ravel(),
                               sampling, slots])

    # -- capacity API (reference engine_v2.py:158–184) ----------------------

    def can_schedule(self, n_tokens: int) -> bool:
        return self.state.can_schedule(n_tokens)

    def query(self) -> Dict[str, int]:
        return {"free_blocks": self.state.allocator.free_blocks,
                "free_sequences": self.config.max_sequences -
                len(self.state.seqs),
                "block_size": self.config.block_size}

    def flush(self, uid: int) -> None:
        self.state.flush(uid)

    # -- the engine step (reference put():107) ------------------------------

    def _put_validated(self, uids: List[int], tokens_list) -> None:
        """Queue tokens for the scheduler, none of them if any would pass
        max_seq_len: past it the page table row would overflow (and
        write_kv's index clamp would misroute KV silently). Totals
        accumulate WITHIN this call too, so duplicate uids in one put()
        can't slip past the check."""
        pending: Dict[int, int] = {}
        for uid, toks in zip(uids, tokens_list):
            have = pending.get(
                uid, len(self.state.seqs[uid].tokens)
                if uid in self.state.seqs else 0)
            total = have + len(np.asarray(toks).reshape(-1))
            if total > self.config.max_seq_len:
                raise ValueError(
                    f"sequence {uid} would reach {total} tokens, over "
                    f"max_seq_len={self.config.max_seq_len}; flush it or "
                    f"raise max_seq_len")
            pending[uid] = total
        self.scheduler.put(uids, tokens_list)

    def put(self, uids: List[int], tokens_list) -> Dict[int, np.ndarray]:
        """Queue new tokens, then run engine steps until every queued token
        has been consumed; returns {uid: last-token logits} for sequences
        whose pending tokens were exhausted this call."""
        return self._put_tokens(uids, tokens_list, mode=None)

    def _put_tokens(self, uids: List[int], tokens_list,
                    mode=("argmax",)) -> Dict[int, Any]:
        """put() for serving: samples ON DEVICE and returns
        {uid: next_token_id} — fetching [n] int32 per step instead of the
        [n, vocab] logits (8 MB/step for a 128k vocab)."""
        self._put_validated(uids, tokens_list)
        out: Dict[int, Any] = {}
        while (res := self.step_with_budget(mode=mode)) is not None:
            out.update(res)
        return out

    def step(self) -> Optional[Dict[int, np.ndarray]]:
        """One ragged forward over the next scheduled batch; None when no
        work is pending."""
        return self.step_with_budget(mode=None)

    def step_with_budget(self, budget: Optional[int] = None,
                         mode=("argmax",)) -> Optional[Dict[int, Any]]:
        """One engine step packing at most ``budget`` tokens (None → the
        scheduler's max_batch_tokens), as a :meth:`launch` and its
        :meth:`collect` back to back: it waits for the program it launched
        and continues no row. {uid: next_token_id} ({uid: logits} with
        mode=None) for the rows whose pending tokens were exhausted, which
        the caller feeds back; None when idle. What :meth:`put` and
        :meth:`step` loop over; a launch still in flight is the caller's to
        collect first."""
        if self._launched:
            raise RuntimeError(
                "step_with_budget waits for its own program: collect() the "
                "launch in flight first")
        batch = self._schedule(budget)
        if batch is None:
            return None
        self._launch(batch, mode)
        return self.collect()[0]

    # -- the step as a launch and a collect ---------------------------------

    @property
    def in_flight(self) -> int:
        """Launches made and not yet collected."""
        return len(self._launched)

    def _schedule(self, budget: Optional[int]) -> Optional[RaggedBatch]:
        with self._tracer.span("serving/schedule"):
            batch = self.scheduler.next_batch(budget=budget)
        if batch is None and not self._launched:
            self._fetch_returned = None
        return batch

    def launch(self, budget: Optional[int] = None, mode=("argmax",),
               row_limits: Optional[Dict[int, int]] = None) -> bool:
        """The first half of a step, up to where the device's work begins:
        schedule, pack, dispatch, count, and the rows' bookkeeping — with
        no wait for the program. False when the scheduler has nothing to
        run. :meth:`collect` is the other half; a caller that launches step
        n+1 BEFORE it collects step n (``ServingFrontend._step``) keeps the
        device at work while the host fetches, fans out and schedules.

        What makes that possible is the CONTINUATION. A decode row's next
        input is the token the program in flight is sampling, and nothing
        else of the next batch depends on its value. So each row of
        ``row_limits`` (uid → tokens it may still emit, as the caller counts
        them: tokens of launches in flight not taken off) whose pending
        tokens this launch exhausts, and that may emit another after this
        one, is extended by ONE placeholder (``FED_SENTINEL``, its page
        allocated as ``state.extend`` does): the next pick sees ``pending
        == 1``, :meth:`_pack` packs the sentinel, and the program reads the
        row's token from the slot buffer (``FED_TOKENS``) the one before it
        wrote. :meth:`collect` patches the placeholder with the fetched
        value. A row without an entry, one at the end of its budget or of
        ``max_seq_len``, and one whose page cannot be allocated are not
        continued: the caller feeds their token back, as after
        :meth:`step_with_budget`. Programs run in launch order over the
        donated arena, so the caller may ``flush`` a continued row at any
        time: its token is dropped at the collect
        (``dispatch/ahead_rows_dropped``)."""
        batch = self._schedule(budget)
        if batch is None:
            return False
        self._launch(batch, mode, row_limits)
        return True

    def _launch(self, batch: RaggedBatch, mode,
                row_limits: Optional[Dict[int, int]] = None) -> None:
        out = self._run(batch, mode=mode)
        with self._tracer.span("serving/retire"):
            self.scheduler.mark_scheduled(batch)
            emits = {}
            for i, uid in enumerate(batch.uids):
                seq = self.state.seqs[uid]
                if seq.pending == 0:
                    emits[uid] = (i, seq, self._continue(seq, row_limits)
                                  if mode is not None else None)
            if self._launched:
                _dispatch_count("dispatch/launches_ahead")
            self._launched.append(_Launch(out, mode, emits))

    def _continue(self, seq, row_limits: Optional[Dict[int, int]]
                  ) -> Optional[int]:
        """Extend ``seq`` by the placeholder of the token its launch is
        sampling, if it may emit another after that one; where the
        placeholder sits in ``seq.tokens``, or None."""
        if row_limits is None or seq.uid not in row_limits:
            return None
        owed = 1 + sum(seq.uid in fl.emits for fl in self._launched)
        if row_limits[seq.uid] <= owed or \
                len(seq.tokens) >= self.config.max_seq_len:
            return None
        try:
            self.state.extend(seq.uid, [FED_SENTINEL])
        except RuntimeError:        # no page: the caller's answer stands
            return None
        return len(seq.tokens) - 1

    def collect(self) -> Optional[Tuple[Dict[int, Any], set]]:
        """The second half of the OLDEST launch in flight: wait for its
        program (``serving/fetch``), patch the continued rows' placeholders
        with the fetched tokens, and return ``({uid: next_token_id}, the
        uids that were continued)`` — or ``{uid: logits}`` with mode=None.
        None with nothing in flight. A row flushed since the launch (or
        whose uid is another sequence's by now) yields nothing."""
        if not self._launched:
            return None
        fl = self._launched.popleft()
        res = np.asarray(self._fetch(fl.out))
        with self._tracer.span("serving/retire"):
            out: Dict[int, Any] = {}
            continued = set()
            dropped = 0
            for uid, (i, seq, at) in fl.emits.items():
                if self.state.seqs.get(uid) is not seq:
                    dropped += at is not None
                elif fl.mode is None:
                    out[uid] = res[i]
                else:
                    out[uid] = int(res[i])
                    if at is not None:
                        seq.tokens[at] = out[uid]
                        continued.add(uid)
            if dropped:
                _dispatch_count("dispatch/ahead_rows_dropped", dropped)
        return out, continued

    def abandon(self) -> None:
        """Forget every launch in flight without waiting for it (after a
        fault: the caller flushes the rows it had continued)."""
        self._launched.clear()

    def _fetch(self, out):
        """``jax.device_get(out)`` under ``serving/fetch``: the pump's one
        wait for the device. Two always-on counters where the wait happens:
        ``dispatch/fetch_wait_seconds`` (seconds inside the ``device_get``)
        and ``dispatch/host_seconds`` (seconds from the last fetch's
        returning to this one's start: everything the host did between two
        fetches, a launch included). Under a caller that waits for each
        launch (:meth:`step_with_budget`) that is time the device idles;
        under one that launches ahead (:meth:`launch` before
        :meth:`collect`) the device runs the next program meanwhile, and it
        is only what the host has to fit inside a program's time. Only
        launches back to back count: :meth:`_schedule` drops the stamp when
        the scheduler has nothing to run and nothing is in flight."""
        with self._tracer.span("serving/fetch"):           # waits for the device
            asked = time.perf_counter()
            got = jax.device_get(out)
            back = time.perf_counter()
            if self._fetch_returned is not None:
                _dispatch_count("dispatch/host_seconds",
                                asked - self._fetch_returned)
            _dispatch_count("dispatch/fetch_wait_seconds", back - asked)
            self._fetch_returned = back
        return got

    def cow_block(self, src_block: int) -> int:
        """Copy-on-write duplicate of one KV page across all layers.

        Prefix-cache handout of a shared PARTIAL last page: the new owner
        will append tokens into that page, so it gets a private copy; full
        shared pages are aliased in the page table instead (no copy).
        Returns the new physical page id (refcount 1, owned by caller).
        """
        if self.model_config.recurrent:
            self._refuse_typed("cow_block (a prefix-cache handout)")
        dst = self.state.allocator.allocate(1)[0]
        if self._copy_pages_fn is None:
            stride = self.config.num_blocks + 1
            self._copy_pages_fn = jax.jit(
                lambda arena, src, dst: {
                    **arena,
                    **pa.copy_pages(_pools(arena), src, dst, stride)},
                donate_argnums=(0,))
        self.arena = self._copy_pages_fn(
            self.arena, jnp.asarray([src_block], jnp.int32),
            jnp.asarray([dst], jnp.int32))
        return dst

    def export_pages(self, blocks: List[int]) -> Dict[str, np.ndarray]:
        """Device→host gather of whole KV pages, every layer's region.

        The serialization half of prefill→decode page handoff
        (serving/handoff.py): ``blocks`` are layer-relative page ids
        (the same ids page tables hold); the flat pool stores layer
        ``l``'s copy of page ``b`` at ``l*(nb+1)+b``, so one fancy-index
        gather per {k, v} pulls all ``L`` copies at once. Returns
        ``{"k", "v"}`` as ``[L, m, bs, kvh*dh]`` host arrays — the
        importing engine must have identical model geometry (it checks).
        """
        self._refuse_typed("export_pages (KV tiering / page handoff)")
        idx = self._page_rows(blocks)
        return {key: np.asarray(self.arena[key][idx]).reshape(
                    self._bundle_shape(key, len(blocks)))
                for key in ("k", "v")}

    def _page_rows(self, blocks: List[int]) -> np.ndarray:
        """Flat pool rows of ``blocks`` in every layer's region, layer by
        layer: [L * m]."""
        L = self.model_config.num_layers
        stride = self.arena["k"].shape[0] // L          # nb + 1
        return (np.arange(L, dtype=np.int32)[:, None] * stride +
                np.asarray(blocks, np.int32)[None, :]).reshape(-1)

    def _bundle_shape(self, key: str, m: int) -> Tuple[int, ...]:
        """A page bundle of ``m`` pages of pool ``key``."""
        return (self.model_config.num_layers, m) + self.arena[key].shape[1:]

    def import_pages(self, pages: Dict[str, np.ndarray],
                     blocks: List[int]) -> None:
        """Scatter pages from :meth:`export_pages` into this engine's
        arena at the (already-allocated, caller-owned) page ids
        ``blocks`` — the adoption half of page handoff. Raises
        ``ValueError`` on a geometry mismatch rather than silently
        writing garbage KV."""
        self._refuse_typed("import_pages (KV tiering / page handoff)")
        idx = self._page_rows(blocks)
        for key in ("k", "v"):
            want = self._bundle_shape(key, len(blocks))
            got = tuple(pages[key].shape)
            if got != want:
                raise ValueError(
                    f"page bundle {key!r} shape {got} does not fit this "
                    f"arena (want {want}) — replicas must share model "
                    f"geometry")
            data = jnp.asarray(pages[key], self.arena[key].dtype) \
                .reshape((len(idx),) + want[2:])
            self.arena[key] = self.arena[key].at[idx].set(data)

    def kv_page_nbytes(self) -> int:
        """Host-side bytes of ONE exported KV page (all layers, k + v) —
        what a tier/handoff consumer budgets per page (the uncompressed
        ``export_pages`` payload size for a single block)."""
        stride = self.config.num_blocks + 1
        return sum(a.nbytes // a.shape[0] * (a.shape[0] // stride)
                   for name, a in _pools(self.arena).items()
                   if not ssm.is_state_pool(name))

    def _refuse_typed(self, what: str) -> None:
        if self.model_config.recurrent:
            raise NotImplementedError(
                f"{what} is not built for a recurrent stack (state-space "
                f"layers, DecoderConfig.layer_kinds 3): a sequence carries "
                f"a state beside its pages, and pages alone are not its "
                f"history")
        if self.model_config.typed:
            raise NotImplementedError(
                f"{what} is not built for a typed layer stack "
                f"(DecoderConfig.layer_kinds): its arena is a pool per "
                f"attention kind, and the page bundle's layout is one "
                f"pool's")

    def _buckets(self, batch: RaggedBatch):
        nb = _bucket(len(batch.uids))
        c = batch.token_ids.shape[1]
        # exactly TWO chunk-width shapes — decode (1) and full prefill
        # chunk: every distinct (n, c) bucket is a fresh XLA compile, and
        # per-width pow2 buckets were costing multiple multi-second
        # compiles per serving session for marginal padding savings
        cb = 1 if c == 1 else self.config.prefill_chunk
        return nb, cb

    def _token_capacities(self, nb: int, cb: int, fresh) -> Tuple[int, ...]:
        """``ragged_forward``'s ``token_capacities`` for the ``(nb, cb,
        fresh)`` step programs — statics derived from what the engine
        knows (the program's rows, chunk and kind, ``max_batch_tokens``),
        so the program grid and its keys stay as they are. The scheduler
        hands a step at most ``max_batch_tokens`` tokens, so where the
        rows hold more slots than that the token-wise sublayers work on
        ``max_batch_tokens`` packed slots; else ``()``, the row form.

        A split program whose rows hold at least FOUR times the budget
        holds a LADDER of instances of its layer loop under the budget:
        16 slots a row (PR 32) and, under that, 8 slots a row — ``(512,
        1024, 2048)`` for the 64 x 128 program's 8,192 row slots, each
        rung half the next, so that no step runs over more than about
        twice its tokens until the budget. A step of decode rows and the
        prompt chunks of a few arrivals is a few hundred tokens: 63 rows
        and one chunk (at most 191) in the reasoning traffic of
        ``benchmark/``, about 61 rows and 2-3 chunks (about 370) in the
        chat traffic (PERF.md §6, PR 46, has the histogram). A rung is
        made only UNDER the next one, and the rung at 8 a row only where
        it holds a whole chunk beside one token of every other row (``8
        nb >= cb + nb - 1``): a smaller one would serve no step that
        carries a full chunk. An instance costs set-up (its layers are
        traced and lowered once more: about 1 s a scanned program, 2-4 s
        an unrolled one, warm; docs/kernels.md), which is why rows at
        twice the budget, already halved by packing, do without, and why
        there is no rung at 4 a row yet.

        Where the rows hold NO MORE than the budget the same ladder serves
        the split program of the engine's FULL row count (``nb`` = the
        bucket of ``max_sequences``: what a loaded replica runs all day),
        topped by the row slots themselves: half and a quarter of them, a
        rung for as long as it holds FOUR whole chunks (its chunk group is
        ``slots // cb`` rows, :func:`_instances`; one of fewer serves too
        few steps to pay for its set-up) — ``(512, 1024, 2048)`` for a
        16-sequence engine at chunk 128, ``(512, 1024)`` for one of eight,
        the row form for one of four. Of 16 such rows two or three carry a
        prompt chunk at a time and the others one token: 200-400 tokens in
        2,048 row slots. The top instance is the packed one with every row
        a chunk row, as in the 64-row program, so the chunk's K/V wait for
        the write-back in one layout at every instance. The smaller row
        buckets of an engine keep the row form — each instance is a layer
        loop more in every warm-up — and a split batch of few rows does
        not stay with them where the full-row program's ladder serves it
        better: :meth:`_pick_form` hands it to THAT program, which is built
        anyway and picks its instance from what the batch holds.
        :meth:`_launch_form` applies the same rule to count the slots."""
        top, rows = self.config.max_batch_tokens, nb * cb
        if cb == 1:
            return ()
        if top >= rows:
            if fresh != "split" or \
                    nb != _bucket(self.config.max_sequences):
                return ()
            ladder = tuple(slots for slots in (rows // 4, rows // 2)
                           if slots >= 4 * cb)
            return ladder + (rows,) if ladder else ()
        if fresh != "split" or 4 * top > rows:
            return (top,)
        ladder = (top,)
        for slots, least in ((16 * nb, 0), (8 * nb, cb + nb - 1)):
            if least <= slots < ladder[0]:
                ladder = (slots,) + ladder
        return ladder

    def _launch_form(self, nb: int, cb: int, fresh, tokens: int,
                     chunk_rows: int) -> launch_work.Form:
        """What the ``(nb, cb, fresh)`` program runs a batch of ``tokens``
        and ``chunk_rows`` over — the device's own rules with ints: the
        instance that holds the batch (:func:`_at_capacity`), or the row
        form's ``nb x cb`` slots of a program without a ladder."""
        capacities = self._token_capacities(nb, cb, fresh)
        instances = _instances(capacities, nb, cb)
        slots, group_rows = instances[_instance_index(
            instances, tokens, chunk_rows)] if instances else (nb * cb, nb)
        return launch_work.Form(
            nb, capacities, slots, group_rows,
            group_rows * cb + nb if group_rows < nb else nb * cb)

    def _pick_form(self, nb: int, cb: int, fresh, tokens: int,
                   chunk_rows: int) -> launch_work.Form:
        """The program a batch of row bucket ``nb`` is packed for, as the
        :class:`launch_work.Form` of its launch: its own bucket's — or, for a
        SPLIT batch of fewer rows than the engine's full bucket, the FULL-ROW
        split program where that one's ladder holds the batch on fewer
        slots. The full-row program is what a loaded replica runs all day,
        so it is built whatever the load; it picks its instance from
        ``counts`` inside the program, and :meth:`_pack` pads a batch to
        any row count with zero-count rows on the trash slot. So a replica
        far under its ``max_sequences`` — 8 long prompts on a 64-sequence
        engine: an 8 x 128 row form of 1,024 slots for about 440 tokens —
        takes the 64-row program's ``(512, 4)`` instance with no program,
        instance or key added. Lifted only if the full-row instance is a
        GROUPED one (never its top, where every padding row becomes a
        chunk row), has strictly fewer token slots than the own program's
        launch and no more attention row slots; a tie stays — the full
        bucket's own batches with it — and a fresh or a decode batch never
        moves, since only a split program's ladder holds a grouped
        instance. Reads what the step holds and what the engine was built
        with, nothing of the model."""
        own = self._launch_form(nb, cb, fresh, tokens, chunk_rows)
        lifted = self._launch_form(_bucket(self.config.max_sequences), cb,
                                   fresh, tokens, chunk_rows)
        if lifted.grouped and lifted.slots < own.slots and \
                lifted.attn_row_slots <= own.attn_row_slots:
            return lifted
        return own

    def _run(self, batch: RaggedBatch, mode=None):
        """One step program over ``batch``: pack and upload, launch, count
        what was launched; the program's tokens (or logits), still on the
        device. The accounting (``serving/count``) comes
        AFTER the jitted call, so the device works while the host counts; a
        launch that raises is therefore not counted."""
        tracer = self._tracer
        with tracer.span("serving/pack"):
            nb, cb = self._buckets(batch)
            # chunk batches avoid the arena READ in attention (the
            # write→read on the ~GB arena serializes the whole layer scan):
            # first-chunk-only batches attend within the chunk ("fresh");
            # continuation / SplitFuse-mixed batches split history
            # (pre-write arena) + within-chunk and merge by logsumexp
            # ("split").
            if cb == 1:
                fresh = False
            elif bool((batch.start_positions == 0).all()):
                fresh = "fresh"
            else:
                fresh = "split"
            tokens = batch.total_tokens
            chunk_rows = int((batch.token_counts > 1).sum())
            form = self._pick_form(nb, cb, fresh, tokens, chunk_rows)
            lifted, nb, capacities = form.nb > nb, form.nb, form.capacities
            if capacities and tokens > capacities[-1]:
                raise ValueError(
                    f"a batch of {tokens} tokens is over max_batch_tokens="
                    f"{self.config.max_batch_tokens}: the {nb}-row step "
                    f"program packs its tokens into that many slots (the "
                    f"scheduler's budget; a budget= / token_budget= above "
                    f"it cannot be served)")
            packed = jnp.asarray(self._pack(batch, nb, cb))  # ONE upload
            program = _step_kind(cb, fresh)
        with tracer.span("serving/dispatch", program=program) as sp:
            out, self._rng_dev, self.arena = self._step_fn(
                nb, cb, mode, fresh)(
                self.params, self.arena, packed, self._rng_dev)
        with tracer.span("serving/count"):
            # span arguments only: nothing to compute for no span
            work = launch_work.launch_work(
                self._site, program, form, cb, batch.start_positions,
                batch.token_counts, span=sp is not None)
            launch_work.count_launch(work, grouped=form.grouped,
                                     lifted=lifted)
            self.last_program = program
            if sp is not None:      # still the recorded event's arguments
                sp.update(work)
        return out

    # -- convenience generation loop ---------------------------------------

    def _validate_lengths(self, prompts, budget_list) -> None:
        """Fail BEFORE any compute when a request cannot fit max_seq_len
        even in principle — the chunked loop would otherwise burn most
        of the workload and then discard every sequence's output."""
        for i, (p, m) in enumerate(zip(prompts, budget_list)):
            total = len(np.asarray(p).reshape(-1)) + max(0, m)
            if total > self.config.max_seq_len:
                raise ValueError(
                    f"generate(): request {i} would reach {total} tokens,"
                    f" over max_seq_len={self.config.max_seq_len}; lower "
                    f"max_new_tokens or raise max_seq_len")

    def generate(self, prompts, max_new_tokens: Union[int, List[int]] = 64,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0) -> List[np.ndarray]:
        """Continuous-batching generation (greedy by default; temperature/
        top-k/top-p sampled on device). ``prompts`` is a list of 1-D int
        arrays (ragged lengths); ``max_new_tokens`` may be per-sequence.
        Returns the full token sequences. A client of :meth:`launch` /
        :meth:`collect`, as the serving frontend's pump is: it launches
        step n+1 before it collects step n, the engine continues each
        decoding row on the device, and a row is flushed at its eos or its
        budget — so a long-tail generation mix pays for the tokens it
        produces, where a padded static batch computes every row out to
        the longest request. A row that ends by its eos one launch after
        it was continued has that launch's token dropped at its collect
        (``dispatch/ahead_rows_dropped``)."""
        if self._launched or \
                any(seq.pending for seq in self.state.seqs.values()):
            # the loop below runs whatever the scheduler holds and keeps
            # only its own rows' tokens: another caller's would be lost
            raise RuntimeError(
                "generate() while sequences of the streaming put() API "
                "have tokens queued or a launch in flight; step them to "
                "the end first")
        if temperature == 0.0:
            mode = ("argmax",)
        else:
            mode = ("sample", int(top_k), top_p < 1.0)
            self._temperature = float(temperature)
            self._top_p = float(top_p)
        # allocate uids that can't collide with sequences the streaming
        # put() API may already hold (review finding: generate() after
        # put([0], ...) silently extended sequence 0)
        base = max(self.state.seqs.keys(), default=-1) + 1
        uids = [base + i for i in range(len(prompts))]
        if isinstance(max_new_tokens, (int, np.integer)):
            remaining = {u: int(max_new_tokens) for u in uids}
        else:
            if len(max_new_tokens) != len(prompts):
                raise ValueError("per-sequence max_new_tokens must match "
                                 "the number of prompts")
            remaining = {u: int(m) for u, m in zip(uids, max_new_tokens)}
        if eos_token_id is None:
            # without eos there is no early exit: a request that cannot
            # fit max_seq_len must fail BEFORE any compute, not after
            # the loop has burned most of the workload
            self._validate_lengths(prompts, [remaining[u] for u in uids])
        seqs = {u: list(np.asarray(p).reshape(-1).astype(np.int32))
                for u, p in zip(uids, prompts)}
        try:
            self._put_validated(uids, [seqs[u] for u in uids])
            # ``remaining`` is each row's ``row_limits`` entry: a length
            # end is known before the launch, so no row is continued past
            # its budget
            while self.in_flight or \
                    self.launch(mode=mode, row_limits=remaining):
                self.launch(mode=mode, row_limits=remaining)    # step n+1
                tokens, continued = self.collect()              # step n
                for u, tok in tokens.items():
                    seqs[u].append(tok)
                    remaining[u] -= 1
                    if remaining[u] <= 0 or tok == eos_token_id:
                        self.flush(u)
                    elif u not in continued:
                        self._put_validated([u], [[tok]])
        finally:
            # a failure mid-loop (arena exhausted, over-length) must not
            # leak this call's sequences — their pages/slots would be lost
            # to every later request — nor leave a launch in flight; after
            # a whole run there is neither
            self.abandon()
            for u in uids:
                self.flush(u)
        return [np.asarray(seqs[u], np.int32) for u in uids]
