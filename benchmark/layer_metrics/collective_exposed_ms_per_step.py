"""Collective time NOT hidden behind compute, per traced step: the time in
which some collective was in flight on device 0 (as
``collective_ms_per_step`` finds them) and no other operation ran
(``trace/scopes.exposed_collective_ns``). The measured replacement of the
program's old ``overlap/fraction`` gauge, which predicted it."""

from benchmark.trace import scopes

LAYER = "sharding"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    steps = run.facts.get("traced_steps")
    if run.trace is None or not steps:
        return None
    win = run.reduce.traced_window(run.trace, run.span_name)
    ns, count = scopes.exposed_collective_ns(run.trace, win)
    if count == 0:
        return None
    return ns / 1e6 / steps
