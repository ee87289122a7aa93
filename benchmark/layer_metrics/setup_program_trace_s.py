"""Seconds jax spent TRACING the step programs' builds: the program's
always-on counter ``compile/trace_seconds`` — the ``jaxpr_trace_duration``
events of the names ``compile_monitor.register_program`` knows (the serving
step programs, the trainer's fused step), the inner functions traced
inside them included once. Python on the host, paid warm and cold alike.

Reads the WHOLE process, not the window: a build in or after the window
would be counted, but the runners fail a run that compiles in its window
(``no_compile_in_window``), and the reference's programs and the scope
table's compiles are ``other``, in no counter. A program without the
counter (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("compile/trace_seconds")
