"""Backend seconds of the step programs' builds that the persistent cache did
NOT answer (a miss, or no cache): the program's always-on counter
``compile/compile_seconds``. 0 in a warm run; a reading over 0 says that
the run compiled cold, whatever its ``setup_s`` looks like.

Reads the WHOLE process, not the window: a build in or after the window
would be counted, but the runners fail a run that compiles in its window
(``no_compile_in_window``), and the reference's programs and the scope
table's compiles are ``other``, in no counter. A program without the
counter (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("compile/compile_seconds")
