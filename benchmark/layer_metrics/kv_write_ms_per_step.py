"""Device self time under the ``kv_write`` scope (``paged_attention.write_kv``
into the flat arena, the K/V relayout copies with it) per traced server
step (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "itl_p95_ms"


def read(run):
    return scopes.scope_ms_per_step(run, ('kv_write',))
