"""Device self time under the ``mlp`` scope (``models/transformer._mlp``: the
gate, up and down projections over every token SLOT of the step, padding
included) per traced server step (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "itl_p95_ms"


def read(run):
    return scopes.scope_ms_per_step(run, ('mlp',))
