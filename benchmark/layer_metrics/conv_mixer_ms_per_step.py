"""Device self time under the ``conv_mixer`` scope (``models/
typed_layers.short_conv_in`` / ``short_conv_out``, ``ops/ssm.conv_rows``: a
gated short convolution's two projections, its two gates and its taps;
layer kind 5 only) per traced server step (``trace/scopes.py``). A program
without the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("conv_mixer",)) or None
