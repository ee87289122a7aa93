"""Idle time of the device outside every ``serving/step`` span: under
``serving/submit`` and in the caller's own loop between two steps (in the
benchmark: the runner's submit / retire), per traced server step
(``lib/host_path.py``)."""

from benchmark.lib import host_path

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    return host_path.idle_ms_per_step(run, "caller")
