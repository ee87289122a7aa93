"""What a latent stack that PICKS ITS KEYS must read and compute for its
two new steps, from shapes: the numerators of ``index_score_roofline`` and
``sparse_latent_attn_roofline``. Kept with the benchmark, beside
``latent_attn_work.py`` (whose decode read is every HELD row).

**Scoring** (a layer that owns an indexer): a query scores every key it can
see — ``index_n_heads`` dot products of ``index_head_dim`` a (query, key)
pair, 2 FLOPs a multiply-add; the ReLU, the heads' weights and their sum
are not matmul work and do not count — and a launch's rows read each
cached index key once a layer that owns an indexer: ``index_head_dim``
values of ``itemsize`` bytes (the pool's true width).

**The picked read** (every latent layer): a query's softmax runs over the
keys picked for it — at most ``index_topk``, all it can see below that —
and in the absorbed form every query head scores the whole cached row (2
FLOPs a value) and sums its first ``kv_lora_rank`` values (2 more a
value), as ``latent_attn_work`` counts a dense read; a launch's rows read
each SELECTED row once a latent layer at the TRUE width (``kv_lora_rank +
qk_rope_head_dim`` values: the lanes a pool is padded by are not useful
bytes). A row can never select more rows than it holds:
:func:`selected_rows` is what the program's ``kv_tokens_selected`` counts,
restated here so that the yardstick's tests hold it.

The two readers also share how they take a traced run's work and time
(:func:`traced_work`, :func:`scope_seconds`).

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
layer_indexer, num_heads, kv_lora_rank, qk_rope_head_dim, index_heads,
index_head_dim, index_topk``."""

from benchmark.lib import latent_attn_work
from benchmark.trace import scopes


def indexer_layers(cfg) -> int:
    return sum(1 for owns in (cfg.layer_indexer or ()) if owns)


def score_flops(cfg, pairs_scored: int) -> float:
    """FLOPs of ``pairs_scored`` (query, key) pairs' index scores — the
    pairs already summed over the layers that own an indexer, as
    ``index_tokens_scored`` counts them."""
    return float(int(pairs_scored) * 2 * int(cfg.index_heads)
                 * int(cfg.index_head_dim))


def score_bytes(cfg, context_tokens: int, itemsize: int = 2) -> float:
    """Bytes of index keys that rows holding ``context_tokens`` cached
    tokens IN ALL must read, over the layers that own an indexer."""
    return float(indexer_layers(cfg) * int(context_tokens)
                 * int(cfg.index_head_dim) * itemsize)


def selected_rows(cfg, contexts) -> int:
    """Rows of the latent pool that rows holding ``contexts`` tokens each
    must read over all latent layers: ``min(context, index_topk)`` a row
    and layer."""
    return latent_attn_work.latent_layers(cfg) * sum(
        min(int(c), int(cfg.index_topk)) for c in contexts)


def picked_bytes(cfg, rows_selected: int, itemsize: int = 2) -> float:
    """Bytes of ``rows_selected`` cached rows (already summed over the
    latent layers, as ``kv_tokens_selected`` counts them) at the true
    width."""
    return float(int(rows_selected) * latent_attn_work.row_values(cfg)
                 * itemsize)


def picked_flops(cfg, pairs_selected: int) -> float:
    """FLOPs of the softmax over ``pairs_selected`` (query, picked key)
    pairs of ONE latent layer, times the latent layers: every head over
    every picked row, scores and weighted sum."""
    per_pair = 2 * int(cfg.num_heads) * (latent_attn_work.row_values(cfg)
                                         + int(cfg.kv_lora_rank))
    return float(latent_attn_work.latent_layers(cfg) * int(pairs_selected)
                 * per_pair)


def traced_work(run, names):
    """Sums of those ``serving/dispatch`` span arguments over the traced
    steps, or None where the run has no traced steps or the first of them
    is never there."""
    rng = run.facts.get("traced_step_range")
    steps = run.program_spans("serving/engine_step")
    if not rng or len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    sums = dict.fromkeys(names, 0)
    for step in steps[rng[0]:rng[1]]:
        for e in scopes.children(events, step, "serving/dispatch"):
            for name in names:
                sums[name] += e.get("args", {}).get(name, 0)
    return sums if sums[names[0]] else None


def scope_seconds(run, wanted):
    """Device-0 self time, in seconds, of the traced window's operations
    under the scopes ``wanted`` in every ``serve_*`` program; 0.0 without a
    device trace or a scope table."""
    dev = scopes.analysis(run)["device"]
    if dev is None:
        return 0.0
    return sum(rec[0] for (program, _instr), rec in dev["ops"].items()
               if program.startswith("serve_") and rec[1] in wanted) / 1e9
