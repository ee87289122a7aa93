"""Device self time under the ``attn_index`` and ``attn_select`` scopes
(``engine_v2``'s ``pick_keys``: an indexer's three projections, its key's
norm and RoPE, the index pool's write, the scores of every visible key;
then the exact top-k and what turns picks into positions or masks) per
traced server step (``trace/scopes.py``). A program without the scopes —
a stack that picks no keys, the parent of the PR that added them — gives
nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ('attn_index', 'attn_select')) \
        or None
