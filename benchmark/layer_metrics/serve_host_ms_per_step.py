"""Host time of a server step: the median, over the window's
``serving/step`` spans that launched a program, of the span less its
``serving/fetch`` child (the ``device_get``: the wait for the device)."""

from benchmark.trace import scopes

LAYER = "entry"
MOVES = "itl_p95_ms"


def read(run):
    events = scopes.program_events(run)
    host = []
    for step in scopes.spans_named(events, "serving/step"):
        fetch = scopes.children(events, step, "serving/fetch")
        if fetch:
            host.append((step["dur"] - sum(f["dur"] for f in fetch)) / 1e3)
    return run.stats.median(host)
