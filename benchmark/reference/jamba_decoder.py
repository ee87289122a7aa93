"""The plain reference for Jamba's stack (AI21 Jamba2-3B, ``model_type:
jamba``), as its published ``config.json`` gives it, WHOLE: every layer,
every width, the full vocabulary. Straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: no kernels, no cache, no
state pool, no batching, no dispatch — one sequence at a time, a LAYER at a
time (a layer's weights are cast to float32 inside its own jitted call and
freed with it), a block of ``TOKEN_BLOCK`` tokens at a time where a part
acts on a token alone, and the selective scan as the PER-TOKEN recurrence
under ``lax.scan``.

EVERY layer ``l`` is a mixer AND a dense MLP under two RMSNorms
(``rms_norm_eps``):

- ``h = RMSNorm(x)``. Layer ``l`` is ATTENTION where ``l % attn_layer_period
  == attn_layer_offset`` (7 and 21 of 28): ``q = h·Wq → [T, Hq, Dh]``, ``k,
  v → [T, KV, Dh]`` (20 query heads over ONE key / value head of 128), NO
  positional term, scores ``q_i·k_j / √Dh`` for ``0 ≤ i − j``, softmax,
  ``(Σ_j p_ij v_j)·Wo`` (``nemotron_h_decoder.attention``: the same block).
  Every other layer is the MAMBA-1 mixer, ``d = mamba_expand · hidden``
  channels, ``N = mamba_d_state``, ``R = mamba_dt_rank``, ``K =
  mamba_d_conv``: ``[x′ | z] = h·W_in`` (``x′`` first); ``u_t = silu(Σ_{i<K}
  w[:, i]·x′_{t−K+1+i} + b)`` over ``x′`` alone; ``[δ_t | B_t | C_t] =
  u_t·W_x``; ``δ ← RMSNorm_R(δ)``, ``B ← RMSNorm_N(B)``, ``C ←
  RMSNorm_N(C)``, each with its learned scale; ``Δ_t = softplus(δ_t·W_dt +
  b_dt)`` (a step size a CHANNEL); ``A = −exp(A_log)``; ``S_t[n, d] =
  exp(Δ_t[d]·A[n, d])·S_{t−1}[n, d] + Δ_t[d]·u_t[d]·B_t[n]``; ``y_t[d] = Σ_n
  S_t[n, d]·C_t[n] + D[d]·u_t[d]``; out ``(y_t ⊙ silu(z_t))·W_out``. No bias
  but the convolution's and ``b_dt``; no norm on the gated output.
- ``x ← x + Mixer(h)``; ``h₂ = RMSNorm(x)``; ``x ← x + W_down·(silu(W_gate·
  h₂) ⊙ W_up·h₂)`` at ``intermediate_size`` (``num_experts: 1``: the
  family's dense MLP in every layer).
- final RMSNorm; ``logits = x·Eᵀ`` over the embedding's rows where
  ``tie_word_embeddings`` (Jamba2-3B), else ``x·W_head``.

Departures from the published module (``transformers``'
``JambaForCausalLM``; whatever could not be confirmed from the catalog row
stands under ``assumed`` in the configuration file): the scan carries ``S``
as ``[N, d]`` and the tree holds ``A_log`` that way (the module's is ``[d,
N]``: a transpose of the same numbers); the tree is the program's (``[in,
out]`` matrices; ``conv_w [d, K]``; the MLP's ``wg`` / ``wi`` / ``wo``;
``dt_bias`` is ``dt_proj``'s bias); long sequences go through the token-wise
parts in blocks (same arithmetic, less memory).

There is no router, so every generated token is judged and
``argmax_gaps`` returns plain logit differences: the tied head at weights
of std 0.02 over 2,560 hidden spreads its logits by ``0.02·√2560`` ≈ 1.0,
the scale the serve runner's near-tie limit was set against.

It reads the program's typed layer tree (``params["layers"]`` is a LIST of
``{ln1, ssm | attn, ln2, mlp}``) and imports nothing from ``deepspeed_tpu``.
It implements the reference contract stated at the top of
``dense_decoder.py``; the attention block, the convolution and the block
helpers are ``nemotron_h_decoder.py``'s, the head and the padding
``dense_decoder.py``'s."""

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense
from benchmark.reference import nemotron_h_decoder as hybrid

TOKEN_BLOCK = hybrid.TOKEN_BLOCK
_f32 = hybrid._f32
_blocks = hybrid._blocks
_padded = hybrid._padded


@dataclass(frozen=True)
class Widths:
    hidden: int
    layer_types: Tuple[str, ...]    # "mamba" or "attention", one a layer
    heads: int
    kv_heads: int
    head_dim: int
    inner: int
    ssm_state: int
    dt_rank: int
    conv_kernel: int
    eps: float
    ffn: int
    vocab: int
    tied: bool

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        if int(hf.get("num_experts", 1)) != 1:
            raise ValueError(f"num_experts {hf['num_experts']!r}: this "
                             f"reference is the dense MLP in every layer")
        period, offset = int(hf["attn_layer_period"]), \
            int(hf["attn_layer_offset"])
        heads = int(hf["num_attention_heads"])
        return cls(
            hidden=int(hf["hidden_size"]),
            layer_types=tuple(
                "attention" if l % period == offset else "mamba"
                for l in range(int(hf["num_hidden_layers"]))),
            heads=heads, kv_heads=int(hf["num_key_value_heads"]),
            head_dim=int(hf["hidden_size"]) // heads,
            inner=int(hf["mamba_expand"]) * int(hf["hidden_size"]),
            ssm_state=int(hf["mamba_d_state"]),
            dt_rank=int(hf["mamba_dt_rank"]),
            conv_kernel=int(hf["mamba_d_conv"]),
            eps=float(hf["rms_norm_eps"]),
            ffn=int(hf["intermediate_size"]), vocab=int(hf["vocab_size"]),
            tied=bool(hf.get("tie_word_embeddings", False)))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies, forward: a ``mamba`` layer's four
    projections (in, ``W_x``, ``W_dt``, out) or an ``attention`` layer's
    four; the MLP's three matrices in EVERY layer; the head over the whole
    vocabulary. (The scan's own products are not matmul parameters.)"""
    qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
    mixer = {"mamba": 3 * w.hidden * w.inner
             + w.inner * (w.dt_rank + 2 * w.ssm_state) + w.dt_rank * w.inner,
             "attention": 2 * w.hidden * qd + 2 * w.hidden * kd}
    return int(sum(mixer[name] + 3 * w.hidden * w.ffn
                   for name in w.layer_types) + w.hidden * w.vocab)


# -- the Mamba-1 mixer ----------------------------------------------------------

def select(w: Widths, p, u):
    """u [T, d] → (Δ [T, d], B [T, N], C [T, N]): the projection, the three
    norms, the step's projection with its bias, softplus."""
    r, n = w.dt_rank, w.ssm_state
    dbc = u @ _f32(p["w_x"])
    norm = lambda x, name: dense._rms_norm(x, _f32(p[name]["scale"]), w.eps)
    delta = norm(dbc[:, :r], "dt_norm") @ _f32(p["w_dt"])
    return jax.nn.softplus(delta + _f32(p["dt_bias"])[None]), \
        norm(dbc[:, r:r + n], "b_norm"), norm(dbc[:, r + n:], "c_norm")


def recurrence(w: Widths, u, delta, b, c, a_log, skip):
    """The selective scan, one token at a time: u, delta [T, d], b, c
    [T, N], ``a_log`` [N, d] → y [T, d]. ``S`` [N, d] starts at 0."""
    a = -jnp.exp(a_log)

    def step(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[None, :] * a) * s + \
            (d_t * u_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + skip * u_t

    s0 = jnp.zeros((w.ssm_state, w.inner), jnp.float32)
    return jax.lax.scan(step, s0, (u, delta, b, c))[1]


def mamba_mixer(w: Widths, p, hin):
    """hin [T, D] (the normed input) → [T, D]."""
    d = w.inner
    xz = _blocks(lambda hb: hb @ _f32(p["w_in"]), hin)
    u = hybrid.conv_silu(xz[:, :d], _f32(p["conv_w"]), _f32(p["conv_b"]))
    delta, b, c = _blocks(lambda ub: select(w, p, ub), u)
    y = recurrence(w, u, delta, b, c, _f32(p["A_log"]), _f32(p["D"]))
    return _blocks(lambda ob: ob @ _f32(p["w_out"]),
                   y * jax.nn.silu(xz[:, d:]))


def mlp(p, hin):
    """hin [T, D] → ``W_down·(silu(W_gate·h) ⊙ W_up·h)``."""
    return _blocks(lambda hb: (jax.nn.silu(hb @ _f32(p["wg"])) *
                               (hb @ _f32(p["wi"]))) @ _f32(p["wo"]), hin)


# -- the stack ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("w", "name"))
def _layer(x, lp, w: Widths, name: str):
    """One layer on one sequence: x [T, D] float32 (T a multiple of the
    token block, or shorter) → x."""
    hin = dense._rms_norm(x, _f32(lp["ln1"]["scale"]), w.eps)
    x = x + (mamba_mixer(w, lp["ssm"], hin) if name == "mamba"
             else hybrid.attention(w, lp["attn"], hin))
    return x + mlp(lp["mlp"],
                   dense._rms_norm(x, _f32(lp["ln2"]["scale"]), w.eps))


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence.
    Sequence-major and a layer at a time: one sequence's stream and one
    layer's float32 weights are alive at a time."""
    emb = params["embed"]["tokens"]
    xs = []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device).astype(
                jnp.float32)
            for name, lp in zip(w.layer_types, params["layers"]):
                x = _layer(x, lp, w, name)
            xs.append(x)
    return xs


def _head_of(w: Widths, params, device):
    """(final norm's scale, the head [D, vocab]) in float32: ``Eᵀ`` where
    the head is tied."""
    head = dense._f32(params["embed"]["tokens"], device).T if w.tied \
        else dense._f32(params["lm_head"], device)
    return dense._f32(params["final_norm"]["scale"], device), head


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' and the
    chip check's side of the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
    scale, head = _head_of(w, params, device)
    with jax.default_matmul_precision("highest"):
        return np.asarray(dense._head(x[:len(tokens)], scale, head, w.eps))


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last)."""
    rows = [np.asarray(r, np.int32) for r in batch]
    total = 0.0
    for r in rows:
        logits = jnp.asarray(logits_of(w, params, r, device))[:-1]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.asarray(r[1:])[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(nll))
    return total / sum(len(r) - 1 for r in rows)


def argmax_gaps(w: Widths, params, prompts, outputs, device) -> np.ndarray:
    """Teacher-forced check of generated tokens: for EVERY generated token
    (flattened over the requests), how far the reference scores it below its
    own argmax at that position (0.0: it IS the argmax)."""
    scale, head = _head_of(w, params, device)
    gaps = []
    for p, o in zip(prompts, outputs):
        (x,) = final_hidden(w, params, [_padded(list(p) + list(o))], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(dense._pow2_at_least(len(o), 64), np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(dense._head(x[at], scale, head,
                                            w.eps))[:len(o)]
        gaps.append(logits.max(axis=-1) -
                    logits[np.arange(len(o)), np.asarray(o)])
    return np.concatenate(gaps)
