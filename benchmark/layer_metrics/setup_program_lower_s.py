"""Seconds jax spent LOWERING the step programs to StableHLO: the program's
always-on counter ``compile/lower_seconds`` (the
``jaxpr_to_mlir_module_duration`` events of the registered names). Python
on the host, paid warm and cold alike.

Reads the WHOLE process, not the window: a build in or after the window
would be counted, but the runners fail a run that compiles in its window
(``no_compile_in_window``), and the reference's programs and the scope
table's compiles are ``other``, in no counter. A program without the
counter (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("compile/lower_seconds")
