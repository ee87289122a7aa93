"""The gated short-convolution mixers' share of their roofline in the
traced launches: the larger of their FLOPs over the bf16 peak and the bytes
they must move over the HBM peak (``lib/short_conv_work``, from the
``slots`` and ``state_rows`` arguments of the program's
``serving/dispatch`` spans inside the traced ``serving/engine_step`` spans)
through ``flops.roofline_share``, over the device self time under the
``conv_mixer`` scope on device 0 — by SCOPE, not by a kernel's name, so the
same work whatever implements the mixer (XLA matmuls and fusions today). A
program without short-convolution layers, the scope or the spans gives
nothing."""

from benchmark.lib import short_conv_work
from benchmark.trace import scopes

LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    rng = run.facts.get("traced_step_range")
    model = run.facts.get("model")
    if run.peaks is None or not rng or \
            not short_conv_work.conv_layers(model):
        return None
    steps = run.program_spans("serving/engine_step")
    if len(steps) != len(run.facts.get("steps", [])):
        return None
    events = scopes.program_events(run)
    launches = slots = rows = 0
    for step in steps[rng[0]:rng[1]]:
        for e in scopes.children(events, step, "serving/dispatch"):
            args = e.get("args", {})
            if "state_rows" not in args:
                continue
            launches += 1
            slots += args["slots"]
            rows += args["state_rows"]
    dev = scopes.analysis(run)["device"]
    if dev is None or not launches:
        return None
    ns = sum(v for (_p, s, _k), v in dev["rows"].items()
             if s == "conv_mixer")
    if not ns:
        return None
    return run.flops.roofline_share(
        short_conv_work.mixer_flops(model, slots),
        short_conv_work.mixer_bytes(model, launches, slots, rows),
        ns / 1e9, run.peaks)
