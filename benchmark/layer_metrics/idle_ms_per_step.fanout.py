"""Idle time of the device under the program's ``serving/fanout`` span (the
token loop: stamp, stream, finish, feed back), per traced server step; by
overlap, piece by piece (``lib/host_path.py``)."""

from benchmark.lib import host_path

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    return host_path.idle_ms_per_step(run, "fanout")
