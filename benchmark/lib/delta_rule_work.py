"""What a launch's GATED DELTA-RULE layers (Qwen3-Next's linear attention;
layer kind 6) must move and compute, from shapes: the numerators of
``delta_rule_roofline``. Kept with the benchmark, beside ``ssm_work.py`` and
``selective_scan_work.py`` (which count the other two kinds of state).

A delta-rule layer carries, for every sequence, a state of ``H_v x d_k x
d_v`` float32 values (``ssm_heads x ssm_state_size x ssm_head_dim``: 32 x 128
x 128 = 2 MiB at Qwen3-Next's widths). Whatever implements the rule, a
launch that advances a row READS that state once and WRITES it once in
every such layer: the bytes below (a one-token row's 2 x 2 MiB, a chunk
row's likewise). What an implementation could avoid moving is left out —
the chunk's own q, k, v, β, g and outputs, the ``[c, c]`` matrices of the
chunk form, the convolution's carried inputs —, so the share may read low
and never over 100%.

The FLOPs are the least ANY form performs for a fed token and value head:
the state's read with ``k`` (``Sᵀk``: the chunk form's ``W·S``), its
rank-one correction (``k ⊗ β(v − r)``: ``Kᵀ·V′``) and its read-out with
``q`` (``Sᵀq``: ``Q·S``), 2 FLOPs a state value each. What only the CHUNK
form performs — ``K·Kᵀ``, ``Q·Kᵀ``, the triangular solve, ``tril(Q·Kᵀ)·V′``:
products of the chunk's width, ``2·c·(2·d_k/R + d_k + 2·d_v)`` a token and
value head more at a chunk of ``c`` positions — is work the recurrence does
not need, and the program's counter holds tokens, not chunk lengths: it is
NOT counted, so the share reads low where chunks are long, never high (the
same rule as ``ssm_work.scan_flops``).

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
ssm_heads, ssm_head_dim, ssm_state_size``."""


def delta_layers(cfg) -> int:
    return sum(1 for kind in cfg.layer_kinds if kind == 6)


def state_values(cfg) -> int:
    """float32 values one sequence carries in one delta-rule layer."""
    return int(cfg.ssm_heads) * int(cfg.ssm_state_size) * \
        int(cfg.ssm_head_dim)


def state_bytes(cfg, state_rows: int, itemsize: int = 4) -> float:
    """Bytes of state that launches advancing ``state_rows`` rows IN ALL
    must read and write, over all delta-rule layers."""
    return float(delta_layers(cfg) * int(state_rows) * 2 *
                 state_values(cfg) * itemsize)


def rule_flops(cfg, tokens: int) -> float:
    """The least FLOPs of ``tokens`` fed tokens' rule, over all delta-rule
    layers: the state read with ``k``, corrected, and read with ``q``."""
    return float(delta_layers(cfg) * int(tokens) * 6 * state_values(cfg))
