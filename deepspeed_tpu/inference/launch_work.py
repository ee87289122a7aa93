"""What ONE launch of a serving step program did, as integers: the useful
work (tokens fed, live KV they attend) against the work attempted (the
slots the program ran over), and what each kind of stack adds to that.
Host arithmetic on the batch's row lengths — no device-side count, no
fetch — from the model's ``DecoderConfig`` and a few integers of the engine
block (:class:`Site`), the :class:`Form` the launch was packed for and the
batch's ``start_positions`` / ``token_counts``: no engine in sight.

:func:`launch_work` is the ``serving/dispatch`` span's arguments;
:func:`count_launch` advances the always-on ``dispatch/*`` counters that
``COUNTED`` names. What each of them MEANS is `docs/observability.md`'s
table. A stack that adds a term adds one function and one entry of ``TERMS``.
"""
from typing import Any, Callable, Dict, NamedTuple, Tuple

import numpy as np

from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.parallel.moe import HELD_ROUND_ROWS


class Form(NamedTuple):
    """What ONE launch of a chunk-width step program runs over, by the
    program's own rules on the host (``RaggedInferenceEngineTPU.
    _launch_form``): the program's rows and ladder, and of the instance the
    batch takes the token slots, the rows of its chunk group and the row
    slots its attention works on."""
    nb: int
    capacities: Tuple[int, ...]
    slots: int
    group_rows: int
    attn_row_slots: int

    @property
    def grouped(self) -> bool:
        return self.group_rows < self.nb


class Launch(NamedTuple):
    """One launch as the terms see it: the program's kind and chunk width,
    whether its instance is a grouped one, the token slots it ran over, the
    rows' tokens (their sum, where each row starts, what each feeds), and
    the rows of the instance's chunk group."""
    program: str
    chunk: int
    grouped: bool
    slots: int
    tokens: int
    start: np.ndarray
    fed: np.ndarray
    group_rows: int = 0


class Site:
    """What every launch of one engine shares: the model, the engine
    block's page size and page-table width, whether the paged kernels read,
    and the K pools' lanes a KV head with the pools' item size (V:
    ``model.v_dim``). Counts the stack's sparse layers and picks the terms
    of ``TERMS`` that apply, once."""

    def __init__(self, model, block_size: int, page_width: int,
                 use_pallas: bool, k_lanes: int = 0, itemsize: int = 2):
        self.model, self.block_size = model, block_size
        self.page_width, self.use_pallas = page_width, use_pallas
        self.k_lanes, self.itemsize = k_lanes, itemsize
        self.sparse_layers = sum(
            model.layer_is_sparse(l) for l in range(model.num_layers)) \
            if model.num_experts else 0
        self.terms = tuple(t for t in TERMS if t.applies(self))


# -- the terms a stack adds ---------------------------------------------------

def kv_window_tokens(site: Site, launch: Launch) -> Dict[str, int]:
    """Tokens of the rows in ONE window layer after the step. Held: every
    token of the row (what a full layer holds too); live: those some query
    of this step can still see, ``min(held, window + fed - 1)`` a row."""
    held = launch.start + launch.fed
    live = np.minimum(held, site.model.sliding_window + launch.fed - 1)
    held = int(held.sum())
    return {"kv_tokens_full": held, "kv_tokens_window_live": int(live.sum()),
            "kv_tokens_window_held": held}


def attn_pairs(site: Site, launch: Launch) -> Dict[str, int]:
    """Live (query, key) pairs in ONE layer of each kind: every fed token
    times the keys it sees — its row up to itself in a full layer, at most
    ``sliding_window`` of them in a window layer — and, of those, the
    pairs INSIDE the fed chunk (``own``: a split step's chunk attention;
    the rest is its history reader's). Span arguments only."""
    w = site.model.sliding_window
    start, fed = launch.start.astype(np.int64), launch.fed.astype(np.int64)

    def seen(first, n):
        """Σ over n queries of min(keys before and at the query, w),
        the first query having ``first`` keys before it."""
        whole = np.clip(w - first, 0, n)   # queries that see them all
        return int((whole * first + whole * (whole + 1) // 2 +
                    (n - whole) * w).sum())

    return {"attn_pairs_full":
                int((fed * start + fed * (fed + 1) // 2).sum()),
            "attn_pairs_window": seen(start, fed),
            "attn_pairs_own_full": int((fed * (fed + 1) // 2).sum()),
            "attn_pairs_own_window": seen(np.zeros_like(start), fed)}


def latent_tokens(site: Site, launch: Launch) -> Dict[str, int]:
    """The cached rows ONE latent layer holds for the batch's rows after
    the launch."""
    return {"kv_tokens_latent": int((launch.start + launch.fed).sum())}


def picked_work(site: Site, launch: Launch) -> Dict[str, int]:
    """Scored: every fed token times the keys it can see, summed over the
    layers that OWN an indexer. Selected: the latent rows the launch's rows
    must read, ``min(context, index_topk)`` a row, times the latent layers.
    Picked pairs: every fed token times the keys picked for it,
    ``min(position + 1, index_topk)``, in ONE latent layer."""
    model = site.model
    start, fed = launch.start.astype(np.int64), launch.fed.astype(np.int64)
    k = model.index_topk
    # of a row's fed tokens, those that still see at most k keys ...
    under = np.clip(k - start, 0, fed)
    return {"index_tokens_scored":
                int((fed * start + fed * (fed + 1) // 2).sum())
                * model.indexer_layers,
            "kv_tokens_selected":
                int(np.minimum(start + fed, k)[fed > 0].sum())
                * model.num_layers,
            # ... pick them all; every later one picks k
            "attn_pairs_selected":
                int((under * start + under * (under + 1) // 2
                     + (fed - under) * k).sum())}


def query_tiles(site: Site, launch: Launch) -> Dict[str, int]:
    """Query tiles of a split launch's history reader in ONE layer and KV
    head, over the rows that reach ``paged_attn_lse`` with a history and a
    token. Held: ``chunk / TILE_Q`` a row of the chunk's width, ONE for a
    grouped instance's one-token row; live: ONE for a row whose queries fit
    the small tile, all of them for a row of more."""
    if launch.program != "split":
        return {}
    model = site.model
    tile_q = pa.tile_queries(launch.chunk, model.num_heads // model.kv_heads)
    fed = launch.fed[(launch.start > 0) & (launch.fed > 0)]
    whole = launch.chunk // tile_q
    held = np.where((fed > 1) | (not launch.grouped), whole, 1)
    return {"query_tiles": int(held.sum()),
            "query_tiles_live": int(np.where(fed <= tile_q, 1, whole).sum())}


def kv_page_work(site: Site, launch: Launch) -> Dict[str, int]:
    """The paged KERNEL's reads over all attention layers (none in a fresh
    step: its chunk is its whole context). Walked: the live pages each
    row's reader must read — a split step's history, a decode step's keys
    up to its own (the uniform stack's ``paged_attn``, a typed stack's
    ``paged_attn_decode`` — in the programs whose rows' gather is worth a
    kernel, ``pa.decode_reads_by_kernel`` of the launch's row bucket: the
    others read by the XLA gather and count nothing), from the window's
    first page in a window layer.
    Fetches: one DMA of K and one of V a page and PROGRAM, a row's pages
    walked by ``kv_heads / heads_per_program`` programs of the block its
    call gives it (one query a row in a decode step and for a grouped
    instance's one-token rows, the chunk's width otherwise)."""
    model, bs, chunk = site.model, site.block_size, launch.chunk
    split = chunk > 1
    if launch.program == "fresh":
        return {}
    start, fed = launch.start, launch.fed
    last = start if split else start + fed
    reads = (last > 0) & (fed > 0)
    wide = (fed > 1) | (split and not launch.grouped)   # the chunk's width
    kinds = model.layer_kinds if model.typed else (0,) * model.num_layers
    walked = fetches = 0
    for kind in (0, 1):
        layers = sum(1 for a in kinds if a == kind)
        if not layers:
            continue
        kvh, window = model.kind_kv_heads(kind), model.kind_window(kind)
        if model.typed and not split and not pa.decode_reads_by_kernel(
                launch.slots, site.page_width, window, bs,
                kvh * (site.k_lanes + model.v_dim) * site.itemsize):
            continue    # this program's rows gather so little: the XLA read
        first = 0
        if window is not None:
            first = np.maximum(start - (window - 1), 0) // bs
        pages = np.where(reads, -(-last // bs) - first, 0)
        groups = model.num_heads // kvh
        programs = np.asarray([kvh // pa.heads_per_program(
            groups * c, kvh, site.k_lanes, model.v_dim, bs, site.itemsize)
            for c in (1, chunk)])
        walked += layers * int(pages.sum())
        fetches += layers * 2 * int((pages * programs[1 * wide]).sum())
    return {"kv_pages_walked": walked, "kv_page_fetches": fetches}


def state_work(site: Site, launch: Launch) -> Dict[str, int]:
    """ONE state-space layer: the rows whose state the launch read and
    wrote, those of them that began at position 0 (zeroed in the program),
    and the tokens that took the chunk form — every token of a launch at
    the chunk's width, but for the one-token rows of a grouped instance,
    which step the recurrence."""
    fed = launch.fed
    if launch.chunk == 1:
        formed = 0
    elif launch.grouped:
        formed = fed[fed > 1].sum()
    else:
        formed = fed.sum()
    return {"state_rows": len(fed),
            "state_resets": int(((launch.start == 0) & (fed > 0)).sum()),
            "ssm_chunk_tokens": int(formed)}


def delta_chunk_positions(site: Site, launch: Launch) -> Dict[str, int]:
    """The positions the gated delta rule's CHUNK form ran over, summed
    over the delta-rule layers. All: the chunk group's rows times the
    chunk's width, what the XLA form computes whatever is live. Live: what
    the kernel walks (``ssm.delta_chunk_kernel``: a row's fed tokens
    rounded up to whole turns of ``ssm.DELTA_KERNEL_SUB``, none for a row
    that feeds none); all of them where the XLA form runs (no kernels, or
    heads that are not whole lane tiles: ``ssm.delta_kernel_takes``)."""
    model = site.model
    if launch.chunk == 1:
        return {}
    layers = sum(1 for kind in model.layer_kinds if kind == 6)
    every = walked = launch.group_rows * launch.chunk
    if site.use_pallas and ssm.delta_kernel_takes(
            model.ssm_state_size, model.ssm_head_dim, launch.chunk):
        fed = launch.fed[launch.fed > 1] if launch.grouped else launch.fed
        sub = ssm.DELTA_KERNEL_SUB
        walked = int((-(-fed // sub) * sub).sum())
    return {"delta_chunk_positions": layers * every,
            "delta_chunk_positions_live": layers * walked}


def moe_assignments(site: Site, launch: Launch) -> Dict[str, int]:
    """Fed tokens x experts a token x sparse layers: what the launch's
    routers hand the experts' dispatch, held here or not."""
    return {"moe_assignments": launch.tokens *
            site.model.num_experts_per_tok * site.sparse_layers}


def moe_buffer_rows(site: Site, launch: Launch) -> Dict[str, int]:
    """The rows the held experts' first round of buffers holds — held
    experts x ``HELD_ROUND_ROWS`` x sparse layers — in a launch of more
    than ``HELD_ROUND_ROWS`` token slots; 0 in one of fewer, where every
    held expert computes every token."""
    return {"moe_buffer_rows": HELD_ROUND_ROWS *
            site.model.num_held_experts * site.sparse_layers *
            (launch.slots > HELD_ROUND_ROWS)}


def hc_maps(site: Site, launch: Launch) -> Dict[str, int]:
    """The hyper-connection maps (a Sinkhorn solve each) the launch ran:
    two a token slot and layer."""
    return {"hc_maps": launch.slots * 2 * site.model.num_layers}


class Term(NamedTuple):
    """One term of a launch's work: the sites whose stack has it, its
    arithmetic, and whether it is span arguments only (not computed for a
    launch whose span is not recorded)."""
    applies: Callable[[Site], Any]
    work: Callable[[Site, Launch], Dict[str, int]]
    span_only: bool = False


def _has_window(site: Site) -> bool:
    return site.model.typed and 1 in site.model.layer_kinds


def _kernel_reads_kv(site: Site) -> bool:
    return site.use_pallas and not site.model.latent    # not mla_decode


#: every term a stack may add, in the order the work's keys are laid down
TERMS = (
    Term(_has_window, kv_window_tokens),
    Term(_has_window, attn_pairs, span_only=True),
    Term(lambda site: site.model.latent, latent_tokens),
    Term(lambda site: site.model.picks_keys, picked_work),
    Term(_kernel_reads_kv, query_tiles),
    Term(_kernel_reads_kv, kv_page_work),
    Term(lambda site: site.model.recurrent, state_work),
    Term(lambda site: site.model.recurrent and site.model.delta_rule,
         delta_chunk_positions),
    Term(lambda site: site.sparse_layers and site.model.num_experts_per_tok,
         moe_assignments),
    # the share's layer (parallel/moe.held_experts_moe_layer)
    Term(lambda site: site.model.typed and site.model.num_experts,
         moe_buffer_rows),
    Term(lambda site: site.model.hc_mult > 1, hc_maps),
)


def launch_work(site: Site, program: str, form: Form, chunk: int,
                start_positions: np.ndarray, token_counts: np.ndarray,
                span: bool = True) -> Dict[str, Any]:
    """The work of one launch of ``program`` (``decode`` / ``fresh`` /
    ``split``) packed for ``form`` at chunk width ``chunk`` over rows that
    start at ``start_positions`` and feed ``token_counts``: the
    ``serving/dispatch`` span's arguments. ``span`` False leaves out the
    terms that are span arguments only."""
    start, fed, bs = start_positions, token_counts, site.block_size
    tokens = int(fed.sum())
    if program == "split" and site.use_pallas:
        # the paged reader walks each row's live pages, then the rows
        # attend their own keys
        context_slots = form.attn_row_slots + int((-(-start // bs)).sum()) * bs
    else:
        context_slots = form.nb * site.page_width * bs
    # the write-back's whole blocks until the tokens are written: a block
    # is the ladder's lowest rung (engine_v2._write_back_slots)
    block = form.capacities[0] if form.capacities else form.nb * chunk
    work = {"program": program, "rows": len(fed), "rows_bucket": form.nb,
            "chunk": chunk, "tokens": tokens, "slots": form.slots,
            "row_slots": form.attn_row_slots,
            "chunk_rows": int((fed > 1).sum()),
            "kv_write_slots": -(-tokens // block) * block,
            "context_tokens": int((start + fed).sum()),
            "context_slots": context_slots}
    launch = Launch(program, chunk, form.grouped, form.slots, tokens, start,
                    fed, form.group_rows)
    for term in site.terms:
        if span or not term.span_only:
            work.update(term.work(site, launch))
    return work


#: key of a launch's work -> the always-on counter it advances, after
#: ``dispatch/``; every other key is a span argument only
COUNTED = {
    "tokens": "tokens", "slots": "token_slots",
    "kv_write_slots": "kv_write_slots", "context_tokens": "context_tokens",
    "context_slots": "context_slots", "chunk_rows": "chunk_rows",
    "row_slots": "attn_row_slots",
    "kv_tokens_window_live": "kv_window_live_tokens",
    "kv_tokens_window_held": "kv_window_held_tokens",
    "index_tokens_scored": "index_tokens_scored",
    "kv_tokens_selected": "kv_tokens_selected",
    "query_tiles": "query_tiles", "query_tiles_live": "query_tiles_live",
    "kv_pages_walked": "kv_pages_walked",
    "kv_page_fetches": "kv_page_fetches",
    "state_rows": "state_rows", "state_resets": "state_resets",
    "ssm_chunk_tokens": "ssm_chunk_tokens",
    "delta_chunk_positions": "delta_chunk_positions",
    "delta_chunk_positions_live": "delta_chunk_positions_live",
    "moe_assignments": "moe_assignments",
    "moe_buffer_rows": "moe_buffer_rows", "hc_maps": "hc_maps"}


def count_launch(work: Dict[str, Any], grouped: bool = False,
                 lifted: bool = False) -> None:
    """Advance the ``dispatch/*`` counters by one launch's ``work``: the
    launch itself (``host_calls``, ``steps.<program>``; a split launch
    also at the slots it ran over, whether it was ``lifted`` to the
    full-row program, and — not under ``steps.`` — whether its instance
    was ``grouped``), then every key ``COUNTED`` names."""
    from deepspeed_tpu.telemetry.registry import registry
    program = work["program"]
    counted = [("host_calls", 1), (f"steps.{program}", 1)]
    if grouped:
        counted.append(("split_grouped_steps", 1))
    if program == "split":
        counted += [(f"split_steps_at.{work['slots']}", 1),
                    ("split_lifted_steps", 1 * lifted)]
    counted += [(COUNTED[key], by) for key, by in work.items()
                if key in COUNTED]
    for name, by in counted:
        registry.counter("dispatch/" + name).inc(by)
