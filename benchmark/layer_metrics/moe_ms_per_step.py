"""Device self time under the ``moe`` scope and the two nested in it
(``moe_router``: the float32 router matmul, sigmoid and top-k;
``moe_experts``: the held experts' gate, up and down matmuls; ``moe``
itself: the dispatch between them — sort, gathers, the combine) per traced
server step (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ('moe', 'moe_router',
                                          'moe_experts'))
