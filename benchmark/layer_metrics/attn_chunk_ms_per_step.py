"""Device self time of the operations under the ``attn_core`` and
``attn_merge`` scopes per traced server step: in a split step the chunk's
OWN causal attention over its row slots and the logsumexp merge of that
partial with the history's (``attn_history_ms_per_step`` has the other
half); in a fresh step the chunk's attention alone; in a decode step the
paged read. Where prompts span many chunks it is what the row form of a
chunk costs: rows x chunk x heads slots whatever the tokens fed."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("attn_core", "attn_merge"))
