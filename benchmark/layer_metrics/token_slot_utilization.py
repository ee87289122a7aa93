"""Useful tokens over token slots: the program's ``dispatch/tokens`` counter
(tokens the packed batches fed: decode tokens and prompt-chunk tokens) over
``dispatch/token_slots`` (bucketed rows x chunk width, what the step program
computes on), both counted at ``engine_v2._run`` where the batch is packed.
Since the engine was built: the ramp and the window (the warm-up grid calls
the step programs beside ``_run`` and counts nothing)."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.counter_ratio("dispatch/tokens", "dispatch/token_slots")
