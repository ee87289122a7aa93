"""What a launch's SELECTIVE-scan layers (Mamba-1; layer kind 4) must move
and compute, from shapes: the numerators of ``selective_scan_roofline``.
Kept with the benchmark, beside ``ssm_work.py`` (which counts the Mamba-2
kind and ``H x P x N`` and is not this kind's).

A selective-scan layer carries, for every sequence, a state of ``N x d``
float32 values (``ssm_state_size x ssm_inner_size``: 16 x 5,120 = 320 KiB
at Jamba2-3B's widths). Whatever implements the scan, a launch that
advances a row READS that state once and WRITES it once in every such
layer: the bytes below. What an implementation could avoid moving is left
out — the chunk's own Δ, u and y (12 bytes a channel and fed token in
float32, but a kernel fused with the selection would never write Δ), the
convolution's carried inputs, ``A`` and ``D`` —, so the share may read low
and never over 100%. The FLOPs are the least ANY form performs for a fed
token: the state's update and its read-out, 2 FLOPs a state value each (as
``ssm_work.scan_flops`` counts the other kind's; the decay's exponential
and its product, which no form avoids either, are not counted as FLOPs of
a matmul peak).

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
ssm_inner_size, ssm_state_size``."""


def selective_layers(cfg) -> int:
    return sum(1 for kind in cfg.layer_kinds if kind == 4)


def state_values(cfg) -> int:
    """float32 values one sequence carries in one selective-scan layer."""
    return int(cfg.ssm_state_size) * int(cfg.ssm_inner_size)


def state_bytes(cfg, state_rows: int, itemsize: int = 4) -> float:
    """Bytes of state that launches advancing ``state_rows`` rows IN ALL
    must read and write, over all selective-scan layers."""
    return float(selective_layers(cfg) * int(state_rows) * 2 *
                 state_values(cfg) * itemsize)


def scan_flops(cfg, tokens: int) -> float:
    """The least FLOPs of ``tokens`` fed tokens' scan, over all
    selective-scan layers: update and read-out of the state."""
    return float(selective_layers(cfg) * int(tokens) * 4 *
                 state_values(cfg))
