"""Mean tokens fed by the traced SPLIT launches (prompt chunks and the
decode rows beside them): the ``tokens`` argument of the program's
``serving/dispatch`` spans with ``program == "split"`` inside the traced
``serving/engine_step`` spans. Under long prompts it is the prefill rate a
launch: the scheduler's token budget and the chunk width bound it, and the
tokens a second follow it."""

from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    rng = run.facts.get("traced_step_range")
    if not rng:
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    fed = [e["args"]["tokens"] for step in steps[rng[0]:rng[1]]
           for e in scopes.children(events, step, "serving/dispatch")
           if e.get("args", {}).get("program") == "split"
           and "tokens" in e["args"]]
    if not fed:
        return None
    return sum(fed) / len(fed)
