"""Operations and bytes the algorithms need, from shapes — the numerators
of ``mfu`` and of every ``*_roofline`` metric. Kept with the benchmark so
no PR that claims a gain can change what counts as useful work.
Recomputed operations never count.

What is particular to an architecture's block — the matmul parameters one
token multiplies — is counted by its reference module
(``matmul_params_per_token``, from the configuration FILE's widths); what
every decoder shares stays here: the attention FLOPs over visible pairs,
the byte counts, the roofline share, and ``matmul_params``, the dense count
that ``reference/dense_decoder.py`` answers with.

``cfg`` is anything with the DecoderConfig's width attributes
(``hidden_size, num_layers, num_heads, kv_heads, head_dim,
intermediate_size, vocab_size, sliding_window``)."""

import json
import os
from typing import Iterable, Optional

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


def matmul_params(cfg) -> int:
    """Parameters that multiply every token: the layers' projections and
    GLU and the untied output head. Norm scales and the embedding lookup
    do no matmul."""
    d, qd = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    kd = cfg.kv_heads * cfg.head_dim
    per_layer = d * qd + 2 * d * kd + qd * d + 3 * d * cfg.intermediate_size
    return cfg.num_layers * per_layer + d * cfg.vocab_size


def causal_pairs(seq_len: int, window: Optional[int]) -> int:
    """(query, key) pairs one sequence's causal attention scores: key j is
    visible to query i when 0 <= i - j < window."""
    t = int(seq_len)
    if window is None or window >= t:
        return t * (t + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (t - w) * w


def attention_flops_fwd(cfg, seq_len: int) -> int:
    """One sequence, ALL layers, forward: QK^T and PV, 2 FLOPs a
    multiply-add, over the visible pairs only."""
    pairs = causal_pairs(seq_len, cfg.sliding_window)
    return cfg.num_layers * 4 * cfg.num_heads * cfg.head_dim * pairs


def train_flops_per_token(cfg, seq_len: int,
                          matmul_params_per_token: Optional[int] = None
                          ) -> float:
    """Forward + backward of one token at this sequence length: 6 FLOPs a
    matmul parameter (the architecture's own count, else the dense one),
    and attention at three times its forward (backward needs dV, dP, dQ,
    dK: four matmuls to the forward's two). No recompute: neither remat's
    nor the flash backward's second QK^T."""
    if matmul_params_per_token is None:
        matmul_params_per_token = matmul_params(cfg)
    return 6.0 * matmul_params_per_token + \
        3.0 * attention_flops_fwd(cfg, seq_len) / seq_len


def flash_train_flops_per_step(cfg, seq_len: int, sequences: int) -> float:
    """What the flash forward and the two backward kernels of one training
    step must compute on ONE chip that holds ``sequences`` sequences:
    forward 2 matmuls + backward 4 over the visible pairs."""
    return 3.0 * attention_flops_fwd(cfg, seq_len) * sequences


def paged_kv_bytes(cfg, context_tokens: int, itemsize: int = 2) -> int:
    """Bytes of K and V that decode rows holding ``context_tokens`` cached
    tokens IN ALL must read, over all layers (each cached token once)."""
    return 2 * cfg.num_layers * cfg.kv_heads * cfg.head_dim * itemsize * \
        int(context_tokens)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> Optional[float]:
    """Least time the chip could take (the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s) over the time it took, in %."""
    if seconds <= 0:
        return None
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
