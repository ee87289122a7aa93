"""Median duration of the program's ``serving/engine_step`` spans whose
step carried decode rows only (no request was waiting for its first
token when the step began, by the runner's own records)."""

LAYER = "step programs"
MOVES = "itl_p95_ms"


def read(run):
    return run.stats.median(run.server_step_ms(decode_only=True))
