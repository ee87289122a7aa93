"""Device self time under the ``hc_maps`` scope alone (``models/
typed_layers.hc_maps``: the RMS norm over a token's whole stream, the
``phi`` product, the activations and the Sinkhorn rounds — the DEPENDENT
steps of a hyper-connection sublayer, which a launch of few rows pays as
latency) per traced server step (``trace/scopes.py``). A program without
the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("hc_maps",)) or None
