"""Mean number of running requests a server step carried: the ``batch``
argument of the program's ``serving/engine_step`` spans over the window."""

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    rows = [e["args"]["batch"] for e in
            run.program_spans("serving/engine_step")
            if "batch" in e.get("args", {})]
    if not rows:
        return None
    return sum(rows) / len(rows)
