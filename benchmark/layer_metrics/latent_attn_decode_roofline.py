"""The absorbed latent attention's share of its roofline in the traced
DECODE steps: the cached rows those steps must read and the scores and
sums they must compute (``lib/latent_attn_work``, from the
``kv_tokens_latent`` argument of the program's ``serving/dispatch`` spans
inside the traced ``serving/engine_step`` spans whose ``program`` is
decode) through ``flops.roofline_share``, over the device self time, in the
``serve_decode*`` programs on device 0, of the operations named
``mla_decode*`` — or, where the program has no such kernel, of everything
under the ``attn_core`` scope. A program without latent layers, or without
the counter, gives nothing."""

from benchmark.lib import latent_attn_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
KERNEL = "mla_decode"


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.peaks is None or not rng or \
            not getattr(model, "latent", False):
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    tokens = 0
    for step in steps[rng[0]:rng[1]]:
        if step.get("args", {}).get("program") != "decode":
            continue
        for e in scopes.children(events, step, "serving/dispatch"):
            tokens += e.get("args", {}).get("kv_tokens_latent", 0)
    dev = scopes.analysis(run)["device"]
    if dev is None or not tokens:
        return None
    mine = {instr: rec for (program, instr), rec in dev["ops"].items()
            if program.startswith("serve_decode")}
    ns = sum(rec[0] for instr, rec in mine.items()
             if instr.startswith(KERNEL)) or \
        sum(rec[0] for rec in mine.values() if rec[1] == "attn_core")
    if not ns:
        return None
    return run.flops.roofline_share(
        latent_attn_work.decode_flops(model, tokens),
        latent_attn_work.decode_bytes(model, tokens), ns / 1e9, run.peaks)
