"""What a launch's state-space layers must move and compute, from shapes:
the numerators of ``ssm_scan_roofline``. Kept with the benchmark, beside
``flops.py``.

A Mamba-2 layer carries, for every sequence, a state of ``H x P x N``
float32 values (``ssm_heads x ssm_head_dim x ssm_state_size``: 2 MiB at
Nemotron 3 Nano's 64 x 64 x 128). Whatever form the scan takes, a launch
that advances a row READS that state once and WRITES it once in every
state-space layer: the bytes below. (The convolution's carried inputs, 36
KiB a row and layer, and the chunk's own x, B, C are left out: under 2% of
the state's bytes.) The FLOPs are the least ANY form performs for a token:
the state's update ``x ⊗ B`` and its read-out ``S·C``, 2 FLOPs a state
value each, and one (query, key) pair of the chunk's own — a chunk of ``n``
live tokens has ``n(n + 1)/2`` pairs, but the program's counter holds
tokens, not chunk lengths, so the pairs beyond a token's own are not
counted: the share reads low where chunks are long, never high.

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
ssm_heads, ssm_head_dim, ssm_groups, ssm_state_size``."""


def ssm_layers(cfg) -> int:
    return sum(1 for kind in cfg.layer_kinds if kind == 3)


def state_values(cfg) -> int:
    """float32 values one sequence carries in one state-space layer."""
    return int(cfg.ssm_heads) * int(cfg.ssm_head_dim) * \
        int(cfg.ssm_state_size)


def state_bytes(cfg, state_rows: int, itemsize: int = 4) -> float:
    """Bytes of state that launches advancing ``state_rows`` rows IN ALL
    must read and write, over all state-space layers."""
    return float(ssm_layers(cfg) * int(state_rows) * 2 * state_values(cfg)
                 * itemsize)


def scan_flops(cfg, tokens: int) -> float:
    """The least FLOPs of ``tokens`` tokens' scan, over all state-space
    layers: update and read-out of the state, and each token's own pair
    (``C·B`` over a group's ``N``, the mix over a head's ``P``)."""
    per_token = 4 * state_values(cfg) + 2 * (
        int(cfg.ssm_groups) * int(cfg.ssm_state_size) +
        int(cfg.ssm_heads) * int(cfg.ssm_head_dim))
    return float(ssm_layers(cfg) * int(tokens) * per_token)
