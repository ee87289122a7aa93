"""Device self time of the operations under the ``attn_history`` scope (the
split program's attention over the PRE-write, read-only arena: the paged
Pallas kernel ``paged_attn_lse`` over the pages each row's history fills
and the query's relayout before it; plain XLA over the gathered extent
where the kernel is not supported) per traced server step:
``trace/scopes.py`` puts each operation of device 0 down to its program by
the enclosing ``XLA Modules`` event and to its scope by the program's own
table (``compile_monitor.scopes``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "itl_p95_ms"


def read(run):
    return scopes.scope_ms_per_step(run, ('attn_history',))
