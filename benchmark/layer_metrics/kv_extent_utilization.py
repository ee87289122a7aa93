"""Live context over the extent attended: the program's
``dispatch/context_tokens`` counter (cached + fed tokens of every row of
every packed batch) over ``dispatch/context_slots`` (bucketed rows x the page
table's width x the page size: what the history attention reads, padding
and trash pages included). Since the engine was built: ramp and window."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "itl_p95_ms"


def read(run):
    return scopes.counter_ratio("dispatch/context_tokens",
                                "dispatch/context_slots")
