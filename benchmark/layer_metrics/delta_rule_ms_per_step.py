"""Device self time under the ``delta_rule`` scope (``ops/ssm.delta_step`` /
``delta_chunk`` and ``delta_inputs``: a gated delta-rule layer's recurrence
in both forms — the one-token pass over a layer's whole state pool, the
chunk's WY form with its triangular solve — and the unit norms, ``β`` and
``g`` that feed it; layer kind 6 only, where kinds 3 and 4 have
``ssm_scan``) per traced server step (``trace/scopes.py``). The layer's
other parts keep the ``ssm_*`` scopes and are read by ``ssm_ms_per_step``.
A program without the scope gives nothing."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "serve_tokens_per_s"


def read(run):
    return scopes.scope_ms_per_step(run, ("delta_rule",)) or None
