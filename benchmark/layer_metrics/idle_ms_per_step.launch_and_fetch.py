"""Idle time of the device from the scheduler's pick to ``device_get``'s
return, outside the program, per traced server step: under the program's
``serving/schedule``, ``serving/pack``, ``serving/dispatch`` and
``serving/count`` spans and under ``serving/fetch`` before its program's
first operation and after its last. ONE metric, because the profile's two
clocks agree to within causality only: where the program lies between
its call and its fetch's return is sure to +/- 0.6 ms, the sum is not
touched by it. The ``host_path`` line prints its three pieces with that
+/- (``lib/host_path.py``)."""

from benchmark.lib import host_path

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    return host_path.idle_ms_per_step(run, "launch_and_fetch")
