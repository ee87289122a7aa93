"""Time in which some collective operation (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute; synchronous or asynchronous) was
in flight on device 0, per traced step: the union of their intervals, hidden
behind compute or not."""

LAYER = "sharding"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    steps = run.facts.get("traced_steps")
    if run.trace is None or not steps:
        return None
    win = run.reduce.traced_window(run.trace, run.span_name)
    seconds, count = run.reduce.collective_seconds(run.trace, win)
    if count == 0:
        return None
    return 1e3 * seconds / steps
