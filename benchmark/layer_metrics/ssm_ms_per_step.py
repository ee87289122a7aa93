"""Device self time under a state-space layer's six scopes (``models/
typed_layers.py`` / ``inference/engine_v2.py``: ``ssm_in`` the input
projection, ``ssm_conv`` the causal convolution, ``ssm_scan`` the scan in
either form, ``ssm_state`` the state pools' gather and scatter,
``ssm_norm`` the gated norm, ``ssm_out`` the output projection) per traced
server step (``trace/scopes.py``). A program without the scopes gives
nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"
SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_state", "ssm_norm",
          "ssm_out")


def read(run):
    return scopes.scope_ms_per_step(run, SCOPES) or None
