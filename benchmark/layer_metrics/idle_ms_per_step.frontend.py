"""Idle time of the device under the frontend's own phases of a step, the
program's spans ``serving/retire``, ``serving/bookkeeping``,
``serving/admit`` and ``serving/plan``, per traced server step
(``lib/host_path.py``)."""

from benchmark.lib import host_path

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    return host_path.idle_ms_per_step(run, "frontend")
