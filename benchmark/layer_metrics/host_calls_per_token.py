"""Host round trips the scheduler spends per generated token: the increase
of the program's ``dispatch/host_calls`` counter over the window, over the
tokens streamed in the window."""

LAYER = "scheduler"
MOVES = "itl_p95_ms"


def read(run):
    tokens = run.facts.get("tokens")
    if not tokens:
        return None
    return run.facts["host_calls"] / tokens
