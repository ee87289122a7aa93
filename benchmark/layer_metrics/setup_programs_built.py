"""Builds of step programs since the process began: the program's always-on
counter ``compile/programs_built`` (one a backend event of a registered
name). In a serving cell the runner's ``grid_warm`` ``programs``; more says
that the ramp or the window met a shape the grid missed, or that a name
was built twice.

Reads the WHOLE process, not the window: a build in or after the window
would be counted, but the runners fail a run that compiles in its window
(``no_compile_in_window``), and the reference's programs and the scope
table's compiles are ``other``, in no counter. A program without the
counter (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("compile/programs_built")
