"""Device self time under the ``attn_latent`` scope (``models/
typed_layers``: what lies between a latent layer's cache row and its
heads — the absorbed query ``q_nope·W_UK``, the heads' outputs ``õ·W_UV``,
a chunk's own ``c·W_kvb``) per traced server step (``trace/scopes.py``). A
program without the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ('attn_latent',)) or None
