"""Share of the traced window in which NO operation ran on the device:
1 - (union of device-op intervals) / (first to last traced step), averaged
over the chips used (``trace/reduce.idle_share``). Source: the profiler's
device trace."""

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    return run.reduce.idle_share(run.trace, run.span_name)
