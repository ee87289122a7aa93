"""Device self time under the ``hc_maps`` and ``hc_mix`` scopes (``models/
typed_layers.hc_maps`` / ``stream_read`` / ``stream_write`` /
``stream_close``: a stream several hidden states wide — the maps of every
sublayer, the gated sums that make the sublayers' inputs, the ``n x n``
write-backs, the readout) per traced server step (``trace/scopes.py``). A
program without the scopes gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("hc_maps", "hc_mix")) or None
