"""What the paged history kernel (``paged_attn_lse``) of a SPLIT step must
read where layers are of two attention kinds with unequal K and V widths:
the numerator of ``paged_attn_lse_roofline``. Kept with the benchmark,
beside ``flops.py`` (whose ``paged_kv_bytes`` assumes one kind of layer and
one head width).

A full layer's history is every cached token of the row; a window layer's
is the tokens before the chunk that some query of the chunk still sees
(at most ``window - 1``). The program counts both per launch where the
batch is packed (``serving/dispatch``: ``kv_tokens_full`` and
``kv_tokens_window_live`` hold cached + fed tokens, ``tokens`` the fed
ones). Each history token is read once per layer of its kind: its KV heads
x (the K head's width + the V head's) elements. The TRUE widths count: the
lanes a K pool is padded by are not useful bytes.

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
head_dim, v_dim, kv_heads, window_kv_heads``."""


def history_bytes(cfg, full_tokens: int, window_tokens: int,
                  itemsize: int = 2) -> float:
    """Bytes of K and V the history attention must read for
    ``full_tokens`` history tokens a full layer and ``window_tokens`` a
    window layer (each summed over the rows), over all layers."""
    per_head = (int(cfg.head_dim) + int(cfg.v_dim)) * itemsize
    full = sum(1 for a in cfg.layer_kinds if a == 0) * int(cfg.kv_heads)
    win = sum(1 for a in cfg.layer_kinds if a == 1) * \
        int(cfg.window_kv_heads or cfg.kv_heads)
    return float(per_head * (full * int(full_tokens) +
                             win * int(window_tokens)))
