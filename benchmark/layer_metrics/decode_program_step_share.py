"""Share of the device program launches that ran a decode-only program:
the program's ``dispatch/steps.decode`` + ``dispatch/steps.megastep``
counters over all ``dispatch/steps.<program>`` (the tag is the engine's
own: which step program ``_run`` / ``_try_megastep`` called, counted where
the batch is packed). Since the engine was built: the ramp and the window,
as the two utilizations read (the traced window's own mix is on the
``host_spans`` line, from the ``serving/dispatch`` spans)."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    steps = scopes.counters_with_prefix("dispatch/steps.")
    total = sum(steps.values())
    if not total:
        return None
    return 100.0 * (steps.get("decode", 0.0) +
                    steps.get("megastep", 0.0)) / total
