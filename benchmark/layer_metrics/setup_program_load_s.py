"""Backend seconds of the step programs' builds that the persistent cache
ANSWERED: the program's always-on counter ``compile/load_seconds`` (the
``backend_compile_duration`` events that followed a ``cache_hits`` event in
their thread): reading the entry, deserialising it, loading the
executable. What a warm start pays for the backend.

Reads the WHOLE process, not the window: a build in or after the window
would be counted, but the runners fail a run that compiles in its window
(``no_compile_in_window``), and the reference's programs and the scope
table's compiles are ``other``, in no counter. A program without the
counter (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("compile/load_seconds")
