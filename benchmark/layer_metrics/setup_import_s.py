"""Seconds from the first to the last line of ``deepspeed_tpu/__init__.py``:
the program's always-on counter ``setup/import_seconds`` (published by the
first ``compile_monitor.install()``; jax's own import is outside it where
the caller imported jax first, as ``benchmark/run.py`` does). Reads the
whole process: the import happens once. A program without the counter (the
parent of PR 54) gives nothing."""

from benchmark.trace import scopes

LAYER = "entry"
MOVES = "setup_s"


def read(run):
    return scopes.counter_value("setup/import_seconds")
