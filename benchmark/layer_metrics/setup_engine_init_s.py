"""Seconds the engines' constructors took on the host: the sum of the
program's always-on counters ``setup/<part>_seconds`` but ``import`` — the
serving engine's ``params`` / ``arena`` / ``engine`` and the frontend's
``frontend``, or the trainer's ``mesh`` / ``params`` / ``optimizer_state`` /
``engine`` (``telemetry.compile_monitor.setup_part``; the parts never
overlap, and a part that only enqueues device work does not wait for it).
Reads the whole process, every engine it built. A program without the
counters (the parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "entry"
MOVES = "setup_s"
PREFIX, SUFFIX = "setup/", "_seconds"


def read(run):
    parts = {name[:-len(SUFFIX)]: seconds for name, seconds in
             scopes.counters_with_prefix(PREFIX).items()
             if name.endswith(SUFFIX) and name != "import" + SUFFIX}
    return sum(parts.values()) if parts else None
