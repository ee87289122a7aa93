"""Device self time under the ``attn_gate`` scope (``models/
typed_layers.typed_attn_out``: a gated attention layer's ``o ⊙ σ(h·W_gate)``
— the gate's projection from the layer's normed input, the sigmoid and the
product a query head and dim; Qwen3-Next's full-attention layers) per
traced server step (``trace/scopes.py``). A program without the scope gives
nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("attn_gate",)) or None
