"""Live context over the extent attended: the program's
``dispatch/context_tokens`` counter (cached + fed tokens of every row of
every packed batch) over ``dispatch/context_slots`` (what the history
attention reads. A split launch under the paged kernel: the pages each
row's history fills, whole, plus bucketed rows x the chunk's keys; every
other launch: bucketed rows x the page table's width x the page size,
padding and trash pages included). Since the engine was built: ramp and
window."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "itl_p95_ms"


def read(run):
    return scopes.counter_ratio("dispatch/context_tokens",
                                "dispatch/context_slots")
