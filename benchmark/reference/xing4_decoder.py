"""The plain reference for Xing4.0's block (XingChen-AGI Xing4.0-29B-A4B;
``model_type: xing4_0``): DeepSeek-V3's latent attention and sigmoid-routed
experts on a residual stream of ``n = hc_mult`` hidden states a token, mixed
by manifold-constrained hyper-connections (mHC, DeepSeek, arXiv:2512.24880,
on Hyper-Connections, arXiv:2409.19606). Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: one sequence at a
time, the stream ``X [T, n, C]`` (``C = hidden_size``), no cache, no
kernels, no batching, no dispatch. The attention's pieces (rotary / YaRN,
the causal softmax), the router, the experts, the routing margin and the
final norm are ``deepseek_v3_decoder``'s and ``dense_decoder``'s; what this
file writes is the residual path and the two branches WITHOUT their
``x +``.

``X₀[i] = E[ids]`` for every ``i < n`` (the embedding copied into the
streams; Hyper-Connections §3). Each layer has TWO sublayers ``s`` —
attention, then the feed-forward part — each with its own ``phi_s [nC, n² +
2n]``, ``base_s [n² + 2n]`` and ``scale_s = (α^pre, α^post, α^res)``; for a
token's ``X``:

- ``x̃ = vec(X)·rsqrt(mean(vec(X)²) + rms_norm_eps)``: an RMS norm over all
  ``nC`` values, no learned scale; ``m = x̃·phi_s``, split into ``m_pre
  [n]``, ``m_post [n]``, ``m_res [n, n]`` (row-major) — and ``base_s`` the
  same way;
- ``H_pre = σ(α^pre·m_pre + b_pre)``; ``H_post = 2·σ(α^post·m_post +
  b_post)``; ``M⁰ = exp(clip(α^res·m_res + b_res, mhc_h_res_clamp_min,
  mhc_h_res_clamp_max))``, then ``hc_sinkhorn_iters`` times: every column
  over (its sum + ``hc_eps``), then every row over (its sum + ``hc_eps``);
  ``H_res`` = the last ``M``, doubly stochastic to the rounds' precision
  (:func:`maps`);
- ``u = Σ_i H_pre[i]·X[i]``; ``y = F_s(RMSNorm_s(u))``: the latent attention
  with ``ln1`` (:func:`attention_branch`), or with ``ln2`` the dense
  SiLU-GLU (layers ``< first_k_dense_replace``) or the routed experts + the
  shared expert (:func:`ffn_branch`; ONE group: ``n_group`` 1);
- ``X'[i] = Σ_j H_res[i, j]·X[j] + H_post[i]·y``.

After the last layer ``x = Σ_i X[i]`` (Hyper-Connections' readout), the
final RMSNorm, ``logits = x·W_head`` (untied).

Not built: the multi-token-prediction module (``num_nextn_predict_layers``
stays in the file as published; no key of the config says how it would
read a stream ``n`` wide).

**What ``argmax_gaps`` judges**: as ``deepseek_v3_decoder`` (same reason,
its ``held_margin``), the tokens whose ROUTING this file's margin decides
(``UNDECIDED_LOGIT_MARGIN``: this block's own reading, below) — with every
expert held a flipped pick still changes which expert ran. The maps have no
margin: they are smooth functions of the stream (no top-k).

It reads the program's typed layer tree (``deepseek_v3_decoder``'s, and a
layer's ``hc_attn`` / ``hc_ffn`` {phi, base, scale}) and imports nothing
from ``deepspeed_tpu``. It implements the reference contract stated at the
top of ``dense_decoder.py``."""

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek_v3_decoder as v3
from benchmark.reference import dense_decoder as dense

#: a token's routing is DECIDED when, in every sparse layer, no move of one
#: router logit smaller than this changes which experts are selected
#: (``deepseek_v3_decoder.held_margin`` with one group and every expert
#: held: the gap between the fourth and the fifth pick, as a logit). With
#: every expert held a flipped pick swaps a WHOLE expert at a weight of
#: about 0.5: the sound bf16 program's logits then stray by 2-4 from this
#: walk's (0.06 at the median), so the undecided tokens cannot be judged.
#: Between two readings on the v5e (PERF.md §6, PR 58; four runs of the
#: cell, 3,152 generated tokens, and 256 steps of ``tools/
#: chip_check_xing4.py``): the sound program's largest gap under this
#: file's argmax reads 0.41-0.65 over the tokens decided by 0.02 (a flipped
#: expert) and 0.027 / 0.025 / 0.067 by 0.04, 0.027 / 0.020 / 0.021 by 0.06,
#: 0.027 / 0.005 / 0.021 by 0.08 — no flip left from 0.04 up —; a program
#: with every matrix rounded to float8 (e4m3) reads 2.71 at 0.08 (56 tokens,
#: 27% of them the argmax) and 1.05 at 0.16 (4 tokens): caught by both of
#: the runner's limits at every margin. 0.08 = twice the least margin with
#: no flip; it leaves 46-66 of a run's 500-1,200 checked tokens (6%).
UNDECIDED_LOGIT_MARGIN = 0.08

#: the head is multiplied a slice of the vocabulary at a time (a float32
#: copy of a 3584 x 131072 head is 1.9 GB: a slice's lives for its call)
HEAD_BLOCK = 16384

_padded = v3._padded


@dataclass(frozen=True)
class Widths:
    """The block's sizes (``deepseek_v3_decoder.Widths``, from the same
    published keys) and the residual path's."""
    block: v3.Widths
    streams: int                    # hc_mult
    rounds: int                     # hc_sinkhorn_iters
    hc_eps: float
    clamp: Tuple[float, float]      # mhc_h_res_clamp_min, _max

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        return cls(block=v3.Widths.from_hf(hf), streams=int(hf["hc_mult"]),
                   rounds=int(hf["hc_sinkhorn_iters"]),
                   hc_eps=float(hf["hc_eps"]),
                   clamp=(float(hf["mhc_h_res_clamp_min"]),
                          float(hf["mhc_h_res_clamp_max"])))


def matmul_params_per_token(w: Widths) -> int:
    """``deepseek_v3_decoder``'s count (every expert is held: the token's
    ``per_token`` experts whole) and, a sublayer, the maps' ``phi`` product
    ``nC x (n² + 2n)``; the ``n x n`` products of the stream are no
    matmuls' parameters."""
    n = w.streams
    return v3.matmul_params_per_token(w.block) + \
        2 * w.block.layers * n * w.block.hidden * (n * n + 2 * n)


@partial(jax.jit, static_argnames=("w",))
def maps(X, hc, w: Widths):
    """X [T, n, C] float32, one sublayer's {phi, base, scale} → ``(H_pre [T,
    n], H_post [T, n], H_res [T, n, n])``."""
    t, n, c = X.shape
    flat = X.reshape(t, n * c)
    xt = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + w.block.eps)
    m = xt @ hc["phi"].astype(jnp.float32)
    a_pre, a_post, a_res = hc["scale"].astype(jnp.float32)
    b = hc["base"].astype(jnp.float32)
    pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a_res * m[:, 2 * n:] + b[2 * n:], *w.clamp)
                  ).reshape(t, n, n)
    for _ in range(w.rounds):
        res = res / (jnp.sum(res, axis=1, keepdims=True) + w.hc_eps)
        res = res / (jnp.sum(res, axis=2, keepdims=True) + w.hc_eps)
    return pre, post, res


@jax.jit
def read(X, pre):
    """``u = Σ_i H_pre[i]·X[i]``: [T, n, C], [T, n] → [T, C]."""
    return jnp.sum(pre[:, :, None] * X, axis=1)


@jax.jit
def write(X, post, res, y):
    """``X'[i] = Σ_j H_res[i, j]·X[j] + H_post[i]·y``."""
    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] * y[:, None]


@partial(jax.jit, static_argnames=("w",))
def attention_branch(u, ln1, a, w: v3.Widths):
    """u [T, C] float32 → attention(RMSNorm(u)) [T, C], expanded form: the
    body of ``deepseek_v3_decoder.attention_block`` without its ``x +``."""
    t = u.shape[0]
    pos = jnp.arange(t)
    a = v3._up(a)
    hin = dense._rms_norm(u, ln1["scale"].astype(jnp.float32), w.eps)
    c_q = dense._rms_norm(hin @ a["wq_a"], a["q_norm"]["scale"], w.eps)
    q = (c_q @ a["wq_b"]).reshape(t, w.heads, w.nope + w.rope)
    kv_a = hin @ a["wkv_a"]
    c = dense._rms_norm(kv_a[:, :w.kv_lora], a["kv_norm"]["scale"], w.eps)
    k_rope = v3._rope(kv_a[:, None, w.kv_lora:], pos, w)       # [T, 1, rope]
    kv = (c @ a["wkv_b"]).reshape(t, w.heads, w.nope + w.v_head)
    q = jnp.concatenate([q[..., :w.nope],
                         v3._rope(q[..., w.nope:], pos, w)], -1)
    k = jnp.concatenate([kv[..., :w.nope],
                         jnp.broadcast_to(k_rope, (t, w.heads, w.rope))], -1)
    o = v3._attention(q, k, kv[..., w.nope:], v3.score_scale(w))
    return o.reshape(t, w.heads * w.v_head) @ a["wo"]


@partial(jax.jit, static_argnames=("w",))
def ffn_branch(u, ln2, lp, w: v3.Widths):
    """u [T, C] → (the layer's feed-forward part on RMSNorm(u) [T, C]: the
    dense SiLU-GLU, or the routed experts' part + the shared expert; the
    layer's routing margin [T], inf for a dense layer): the bodies of
    ``deepseek_v3_decoder.dense_block`` / ``sparse_block`` without their
    ``x +``."""
    hin = dense._rms_norm(u, ln2["scale"].astype(jnp.float32), w.eps)
    if "moe" not in lp:
        m = lp["mlp"]
        return v3._glu(hin, m["wg"], m["wi"], m["wo"]), \
            jnp.full(u.shape[0], jnp.inf, jnp.float32)
    out = v3.experts_part(hin, lp["moe"], w)
    if "shared" in lp:
        s = lp["shared"]
        out = out + v3._glu(hin, s["wg"], s["wi"], s["wo"])
    return out, v3.held_margin(hin, lp["moe"], w)


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the readout of the last layer's stream [T, C]
    float32 (what the final norm reads), and each position's least routing
    margin over the sparse layers [T]."""
    emb = params["embed"]["tokens"]
    xs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device
                               ).astype(jnp.float32)
            X = jnp.repeat(x[:, None], w.streams, axis=1)
            margin = jnp.full(len(r), jnp.inf, jnp.float32)
            for lp in params["layers"]:
                pre, post, res = maps(X, lp["hc_attn"], w)
                X = write(X, post, res, attention_branch(
                    read(X, pre), lp["ln1"], lp["attn"], w.block))
                pre, post, res = maps(X, lp["hc_ffn"], w)
                branch = {k: lp[k] for k in ("mlp", "moe", "shared")
                          if k in lp}
                y, m = ffn_branch(read(X, pre), lp["ln2"], branch, w.block)
                X = write(X, post, res, y)
                margin = jnp.minimum(margin, m)
            xs.append(jnp.sum(X, axis=1))
            margins.append(margin)
    return xs, margins


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    return hidden_and_margins(w, params, token_rows, device)[0]


def _logits(w: Widths, params, x, device) -> np.ndarray:
    """x [T, C] (the stream's readout) → [T, vocab] float32 on the host: the
    final norm and the untied head, a slice of the vocabulary at a time."""
    scale = dense._f32(params["final_norm"]["scale"], device)
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return np.concatenate([
            np.asarray(v3._head(x, scale, head[:, at:at + HEAD_BLOCK],
                                w.block.eps))
            for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (T is padded and cut
    back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
    return _logits(w, params, x, device)[:len(tokens)]


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last). No balance term (``noaux_tc``)."""
    total, count = 0.0, 0
    for r in (np.asarray(r, np.int32) for r in batch):
        logits = jnp.asarray(logits_of(w, params, r, device))[:-1]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.asarray(r[1:])[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(nll))
        count += len(r) - 1
    return total / count


def teacher_forced(w: Widths, params, prompts, outputs, device):
    """Every generated token of every request, flattened: how far the
    reference scores it below its own argmax at that position (``gap``) and
    the position's routing margin (``margin``)."""
    gaps, margins = [], []
    for p, o in zip(prompts, outputs):
        row = _padded(list(p) + list(o))
        (x,), (margin,) = hidden_and_margins(w, params, [row], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(-(-len(o) // v3.PAD_TO) * v3.PAD_TO, np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        logits = _logits(w, params, x[at], device)[:len(o)]
        gaps.append(logits.max(axis=-1) -
                    logits[np.arange(len(o)), np.asarray(o)])
        margins.append(np.asarray(margin)[at[:len(o)]])
    return {"gap": np.concatenate(gaps), "margin": np.concatenate(margins)}


def argmax_gaps(w: Widths, params, prompts, outputs, device) -> np.ndarray:
    """Teacher-forced check of generated tokens: for every generated token
    whose routing is decided (the module docstring; flattened over the
    requests), how far the reference scores it below its own argmax at
    that position (0.0: it IS the argmax)."""
    seen = teacher_forced(w, params, prompts, outputs, device)
    return seen["gap"][seen["margin"] >= UNDECIDED_LOGIT_MARGIN]
