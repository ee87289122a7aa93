"""Share of the cached rows the launches' rows HOLD that their queries
must read: ``dispatch/kv_tokens_selected`` (``min(context, index_topk)`` a
row and latent layer, ``engine_v2._picked_work``) ÷ (``dispatch/
context_tokens`` x the latent layers), ramp and window. How sparse the
traffic made the read: 100% while every context is under ``index_topk``
(the dense latent stack), ``index_topk`` ÷ the mean context beyond. A
program without the counter gives nothing."""

from benchmark.lib import latent_attn_work
from benchmark.trace import scopes

LAYER = "scheduler"
MOVES = "serve_tokens_per_s"


def read(run):
    model = run.facts.get("model")
    selected = scopes.counter_value("dispatch/kv_tokens_selected")
    held = scopes.counter_value("dispatch/context_tokens")
    if selected is None or not held or \
            not getattr(model, "layer_indexer", None):
        return None
    return 100.0 * selected / (held * latent_attn_work.latent_layers(model))
