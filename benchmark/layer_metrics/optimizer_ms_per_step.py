"""Device self time under the ``optimizer`` and ``grad_clip`` scopes
(``engine._apply_update``: unscale, global norm, clip, AdamW) per traced
training step (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    return scopes.scope_ms_per_step(run, ('optimizer', 'grad_clip'))
