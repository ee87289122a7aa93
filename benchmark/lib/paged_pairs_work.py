"""What the paged history kernel (``paged_attn_lse``) of a SPLIT step must
COMPUTE where the rows carry chunks of live queries: the FLOPs of
``paged_attn_lse_pairs_roofline``. Kept with the benchmark, beside
``paged_hist_work.py`` (the bytes the same kernel must read).

A (query, key) pair costs a query head ``2 x head_dim`` FLOPs for its score
and ``2 x v_dim`` for its share of the weighted sum. The program counts
the live pairs of a launch per layer of each kind where the batch is
packed (``serving/dispatch``: ``attn_pairs_full`` / ``attn_pairs_window``
hold every fed token x the keys it sees, ``attn_pairs_own_full`` /
``attn_pairs_own_window`` the part inside the fed chunk, which the chunk's
own attention takes and not the history kernel); the history kernel's
pairs are the difference. Masked pairs of a page the kernel walks, and the
dead query rows of a row slot, are not useful work and do not count.

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
num_heads, head_dim, v_dim``."""


def history_pairs(args: dict):
    """(full, window) history pairs of one launch from its
    ``serving/dispatch`` span's arguments; None where the program does not
    count them."""
    names = ("attn_pairs_full", "attn_pairs_own_full", "attn_pairs_window",
             "attn_pairs_own_window")
    if any(n not in args for n in names):
        return None
    full, own_full, window, own_window = (int(args[n]) for n in names)
    return full - own_full, window - own_window


def history_flops(cfg, full_pairs: int, window_pairs: int) -> float:
    """FLOPs the history attention must do for ``full_pairs`` (query, key)
    pairs a full layer and ``window_pairs`` a window layer, over all
    layers and query heads: QK^T and PV, 2 FLOPs a multiply-add."""
    per_pair = 2 * int(cfg.num_heads) * (int(cfg.head_dim) + int(cfg.v_dim))
    full = sum(1 for a in cfg.layer_kinds if a == 0)
    win = sum(1 for a in cfg.layer_kinds if a == 1)
    return float(per_pair * (full * int(full_pairs) +
                             win * int(window_pairs)))
