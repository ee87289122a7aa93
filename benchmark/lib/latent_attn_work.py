"""What the absorbed latent attention of a DECODE step must read and
compute, from shapes: the numerators of ``latent_attn_decode_roofline``.
Kept with the benchmark, beside ``flops.py`` (whose ``paged_kv_bytes``
assumes K and V heads).

A latent layer (DeepSeek-V3's MLA) caches ONE row a token: the normed
latent (``kv_lora_rank`` values) and the rotary key all heads share
(``qk_rope_head_dim``). A decode row reads every cached row of its
sequence once a latent layer — its own, just written, included — and, in
the absorbed form, every query head scores the whole row (2 FLOPs a value)
and sums its first ``kv_lora_rank`` values under the softmax (2 more a
value). The TRUE widths count: the lanes a pool is padded by are not useful
bytes, and the products with ``W_UK`` / ``W_UV`` on either side of the
attention are not the attention's (the ``attn_latent`` scope holds them).

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
num_heads, kv_lora_rank, qk_rope_head_dim``."""


def latent_layers(cfg) -> int:
    return sum(1 for kind in cfg.layer_kinds if kind == 2)


def row_values(cfg) -> int:
    """Values one cached token holds in one latent layer."""
    return int(cfg.kv_lora_rank) + int(cfg.qk_rope_head_dim)


def decode_bytes(cfg, kv_tokens: int, itemsize: int = 2) -> float:
    """Bytes of cached rows that decode rows holding ``kv_tokens`` cached
    tokens IN ALL must read, over all latent layers (each row once)."""
    return float(latent_layers(cfg) * int(kv_tokens) * row_values(cfg)
                 * itemsize)


def decode_flops(cfg, kv_tokens: int) -> float:
    """FLOPs of those rows' scores and weighted sums, every head over
    every cached row, over all latent layers."""
    per_row = 2 * int(cfg.num_heads) * (row_values(cfg) +
                                        int(cfg.kv_lora_rank))
    return float(latent_layers(cfg) * int(kv_tokens) * per_row)
