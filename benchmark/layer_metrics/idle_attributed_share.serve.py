"""Share of the device's idle time, in %, that ``lib/host_path.py`` could
put down to a leaf span, a program or the caller: 1 - (idle time under
``serving/step`` / ``serving/engine_step`` but under no leaf) / idle time.
The guard of the four ``idle_ms_per_step.*``, as ``scope_coverage.serve``
is the scopes': it reads low where a stretch of the pump has no span."""

from benchmark.lib import host_path

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    return host_path.attributed_share(run)
