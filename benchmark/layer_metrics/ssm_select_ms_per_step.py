"""Device self time under the ``ssm_select`` scope (``models/
typed_layers.ssm_select``: what turns a selective-scan layer's convolved
channels into its scan's inputs — ``[δ | B | C] = u·W_x``, the three inner
RMSNorms, ``Δ = softplus(δ·W_dt + b_dt)``; layer kind 4 only) per traced
server step (``trace/scopes.py``). A program without the scope gives
nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("ssm_select",)) or None
