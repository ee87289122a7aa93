"""The plain reference for LFM2-MoE's stack (Liquid AI LFM2-24B-A2B,
``model_type: lfm2_moe``), as its published ``config.json`` gives it, on ONE
CHIP'S SHARE of an expert-parallel deployment. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernels, no cache, no pool of tails, no batching, no dispatch — one
sequence at a time, a block of ``TOKEN_BLOCK`` tokens at a time where a
part acts on a token alone, every held expert computed for every token and
weighed (0 where the token did not pick it), the convolution over the WHOLE
sequence at once from zeros before it.

The equations (HF ``transformers`` ``models/lfm2_moe``). ``x₀ = E[ids]``.
Layer ``l``, ``layer_types[l]`` naming its mixer:

- ``h = RMSNorm(x)`` (``operator_norm``, ``norm_eps``).
- ``conv``: ``[B | C | x̃] = h·W_in`` (three blocks of ``hidden_size``, IN
  THAT ORDER, no bias); ``u = B ⊙ x̃``; ``c_t = Σ_{i<K} w[:, i] ⊙
  u_{t−K+1+i}`` with ``K = conv_L_cache`` — depthwise, causal, no bias, NO
  activation, inputs before the sequence 0; ``y = C ⊙ c``; ``x ← x +
  y·W_out``.
- ``full_attention``: ``q = h·W_q → [Hq, Dh]``, ``k, v → [KV, Dh]``; ``q ←
  RMSNorm_Dh(q)``, ``k ← RMSNorm_Dh(k)`` (one learned scale of ``Dh`` each,
  shared by the heads, ``norm_eps``) BEFORE rotate-half RoPE (θ of
  ``rope_parameters``, the whole head); causal softmax of ``q·k / √Dh``;
  query head ``h`` reads KV head ``h // (Hq / KV)``; ``x ← x + o·W_o``. No
  bias anywhere.
- ``h₂ = RMSNorm(x)`` (``ffn_norm``). ``l < num_dense_layers``: ``x ← x +
  W₂(silu(W₁h₂) ⊙ W₃h₂)`` at ``intermediate_size``. Else ``s = σ(h₂·W_r)``
  (``num_experts`` scores, float32); ``pick = s + expert_bias``
  (``use_expert_bias``); the ``num_experts_per_tok`` highest ``pick``; ``g =
  s[those] / (Σ s[those] + 1e-6) × routed_scaling_factor``
  (``norm_topk_prob``) — the bias moves the PICK and never the weight; ``x ←
  x + Σ_{e ∈ picked ∩ held} g_e·GLU_e(h₂)``, SiLU-GLUs of
  ``moe_intermediate_size``; no shared expert.
- final RMSNorm (``embedding_norm``); ``logits = x·Eᵀ``: a TIED head (the
  published file has no ``tie_word_embeddings`` and HF's ``Lfm2MoeConfig``
  ties by default: ``assumed`` in the configuration file).

**The share**: ``num_experts`` stays the router's published width;
``expert_share`` = ``{"router_experts", "first_expert", "held_experts"}``
(not a published key) says which experts this chip holds. What the absent
experts would add is left out, here as in the program, and the partial
result goes on to the next layer.

Departures from the published module: attention in blocks of
``TOKEN_BLOCK`` queries against all keys (the same arithmetic); the tree is
the program's (``[in, out]`` matrices; ``conv_w [hidden, K]``; the held
experts stacked).

**What ``argmax_gaps`` judges**: the generated tokens whose ARGMAX this
reference decides — its best logit leads its second by
``UNDECIDED_ARGMAX_MARGIN`` (after ``glm_moe_dsa_decoder.py``'s margin of
that name) — and leaves the others out. The constant's comment has the
sizing and the readings: a token here passes 38 routers and 80 branch sums,
and a sound bf16 program moves a logit difference by more than the runner's
near-tie tolerance whatever its routing margins are.

It reads the program's typed layer tree (``params["layers"]`` is a LIST of
``{ln1, conv | attn, ln2, mlp | moe}``) and imports nothing from
``deepspeed_tpu``. It implements the reference contract stated at the top
of ``dense_decoder.py``; the padding helpers and the rotary term are that
file's."""

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense

#: tokens a block of a token-wise part holds at once (a dense layer's
#: [block, 11,776] intermediates, the attention's scores)
TOKEN_BLOCK = 256

#: a generated token is JUDGED when this reference's best logit at its
#: position leads the second best by at least this much: the program then
#: has to have sampled exactly that token (a gap of 0.0), and a program that
#: did not scores at least the lead, over the runner's 0.25. Why not every
#: token, and why not the tokens whose ROUTING is decided (ISSUE 56's plan,
#: ``nemotron_h_decoder.held_margin``'s pattern: tried first, ``PERF.md`` §6,
#: PR 56). Served in bf16 this stack is 80 branch sums deep and routes 38
#: times a token; upstream rounding flips a held expert's membership in one
#: token in two, and a flip at the position, at the two before it (whose
#: ``u`` the next convolutions' taps read at full weight), or further back
#: through 30 stacked convolutions and ten attention layers moves a logit
#: DIFFERENCE by up to 0.49 (ten runs of the cell on the v5e, 8,000 tokens:
#: 87% are this reference's argmax, the others lie 0.045 under it at the
#: median, 0.17 at the 90th percentile, 0.31 at the 99th). With every held
#: expert's membership decided by 0.02 logits at the position and 0.005 at
#: the two before it (10% of tokens) the largest gap still read 0.086 / 0.349 / 0.205 / 0.169 / 0.114
#: / 0.057 / 0.283 / 0.098 / 0.082 / 0.206 over ten runs — two of ten NOT
#: ``correct`` — and 0.21 at 0.03 / 0.01 (3% of
#: tokens): the rounding of 80 branch sums alone reaches the tolerance, as
#: Jamba's 56 came within 0.07 of it (PR 49). By this reference's own lead
#: (five runs, 3,773 tokens): of 1,081 tokens that lead by 0.3 two were
#: missed (leads 0.35), of 746 by 0.4, 504 by 0.5, 340 by 0.6 and 210 by
#: 0.75 NONE; the misses thin out tenfold for every 0.155 of lead, which
#: puts a false run at one in twenty at 0.4, one in 700 at 0.6 and under one
#: in 5,000 at 0.75. At 0.75 a run judges 32–66 tokens of its eight
#: requests' 520–940 (the count is ``checked_tokens`` on the runner's
#: ``checks`` line). The nearest precision below: every weight matrix in
#: float8 reads a gap of 4.29 and 4% exact (``tools/chip_control_lfm2.py``).
UNDECIDED_ARGMAX_MARGIN = 0.75


@dataclass(frozen=True)
class Widths:
    hidden: int
    kinds: Tuple[str, ...]          # one name a layer: conv, full_attention
    dense_layers: int
    heads: int
    kv_heads: int
    conv_kernel: int
    eps: float
    theta: float
    dense_ffn: int
    expert_ffn: int
    router_experts: int
    first_expert: int
    held_experts: int
    per_token: int
    norm_topk: bool
    routed_scale: float
    vocab: int

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        layers = int(hf["num_hidden_layers"])
        kinds = tuple(hf["layer_types"][:layers])
        if len(kinds) != layers or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {kinds!r} for {layers} layers: "
                             f"conv and full_attention")
        if hf.get("conv_bias", False):
            raise ValueError("conv_bias true is not this reference's block")
        experts = int(hf["num_experts"])
        share = hf.get("expert_share") or {
            "router_experts": experts, "first_expert": 0,
            "held_experts": experts}
        return cls(
            hidden=int(hf["hidden_size"]), kinds=kinds,
            dense_layers=int(hf.get("num_dense_layers", 0)),
            heads=int(hf["num_attention_heads"]),
            kv_heads=int(hf["num_key_value_heads"]),
            conv_kernel=int(hf["conv_L_cache"]),
            eps=float(hf["norm_eps"]),
            theta=float(hf["rope_parameters"]["rope_theta"]),
            dense_ffn=int(hf["intermediate_size"]),
            expert_ffn=int(hf["moe_intermediate_size"]),
            router_experts=int(share["router_experts"]),
            first_expert=int(share["first_expert"]),
            held_experts=int(share["held_experts"]),
            per_token=int(hf["num_experts_per_tok"]),
            norm_topk=bool(hf.get("norm_topk_prob", True)),
            routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
            vocab=int(hf["vocab_size"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies ON THIS CHIP, forward: a convolution
    mixer's two projections (``hidden x 3·hidden`` and ``hidden x hidden``;
    the taps are no matmul), an attention layer's four; a dense layer's
    three matrices; a sparse layer's router at its full width and, of the
    token's ``per_token`` experts, the share that is held here (three
    matrices each); the tied head over the whole vocabulary."""
    d = w.hidden
    qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
    mixer = {"conv": 4 * d * d, "full_attention": 2 * d * qd + 2 * d * kd}
    sparse = d * w.router_experts + round(
        w.per_token * w.held_experts / w.router_experts * 3 * d * w.expert_ffn)
    return int(sum(mixer[kind] + (3 * d * w.dense_ffn if l < w.dense_layers
                                  else sparse)
                   for l, kind in enumerate(w.kinds)) + d * w.vocab)


def _f32(a):
    return a.astype(jnp.float32)


def _blocks(fn, *rows):
    """``fn`` over blocks of ``TOKEN_BLOCK`` rows of the ``[T, ...]``
    operands (T a multiple of the block, or shorter than one)."""
    t = rows[0].shape[0]
    blk = min(t, TOKEN_BLOCK)
    out = jax.lax.map(lambda args: fn(*args), tuple(
        r.reshape((t // blk, blk) + r.shape[1:]) for r in rows))
    return jax.tree.map(lambda o: o.reshape((t,) + o.shape[2:]), out)


# -- conv: the gated short convolution ---------------------------------------

def short_conv(u, conv_w):
    """u [T, D] → ``c_t = Σ_i w[:, i] ⊙ u_{t−K+1+i}``; inputs before the
    sequence are 0. No bias, no activation."""
    k = conv_w.shape[1]
    t = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return sum(padded[i:i + t] * conv_w[:, i][None] for i in range(k))


def conv_mixer(w: Widths, p, hin):
    """hin [T, D] (the normed input) → [T, D]."""
    d = w.hidden
    bcx = _blocks(lambda hb: hb @ _f32(p["w_in"]), hin)
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    y = c * short_conv(b * x, _f32(p["conv_w"]))
    return _blocks(lambda yb: yb @ _f32(p["w_out"]), y)


# -- full_attention: GQA under q / k head norms and RoPE ---------------------

def attention(w: Widths, p, hin):
    """hin [T, D] → [T, D]; causal, grouped-query; every q and k head under
    its RMSNorm BEFORE the rotary term."""
    t = hin.shape[0]
    pos = jnp.arange(t)
    per = w.heads // w.kv_heads
    k = (hin @ _f32(p["wk"])).reshape(t, w.kv_heads, w.head_dim)
    k = dense._rope(dense._rms_norm(k, _f32(p["k_norm"]["scale"]), w.eps),
                    pos, w.theta)
    v = (hin @ _f32(p["wv"])).reshape(t, w.kv_heads, w.head_dim)
    blk = min(t, TOKEN_BLOCK)

    def block(hb, qpos):
        q = (hb @ _f32(p["wq"])).reshape(blk, w.heads, w.head_dim)
        q = dense._rope(dense._rms_norm(q, _f32(p["q_norm"]["scale"]),
                                        w.eps), qpos, w.theta)
        q = q.reshape(blk, w.kv_heads, per, w.head_dim)
        s = jnp.einsum("qgpd,kgd->gpqk", q, k) * (w.head_dim ** -0.5)
        ok = qpos[:, None] >= pos[None]
        pr = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gpqk,kgd->qgpd", pr, v)
        return o.reshape(blk, w.heads * w.head_dim) @ _f32(p["wo"])

    return _blocks(block, hin, pos)


# -- the feed-forward parts --------------------------------------------------

def _glu(hin, wg, wi, wo):
    return (jax.nn.silu(hin @ _f32(wg)) * (hin @ _f32(wi))) @ _f32(wo)


def route(hin, m, w: Widths):
    """hin [T, D] → the weight of every one of the router's experts for
    every token [T, router_experts] (0 where not selected), and the
    selected ids [T, per_token]."""
    s = jax.nn.sigmoid(hin @ _f32(m["router"]))
    pick = s + _f32(m["router_bias"]) if "router_bias" in m else s
    _, sel = jax.lax.top_k(pick, w.per_token)
    kept = jnp.take_along_axis(s, sel, axis=-1)
    if w.norm_topk:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-6)
    kept = kept * w.routed_scale
    chosen = jax.nn.one_hot(sel, w.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", kept, chosen), sel


def experts_part(hin, m, w: Widths):
    """The part of the routed sum that the HELD experts give: hin [T, D] →
    [T, D]. With every expert held it is the whole routed sum. One expert's
    weights are cast to float32 at a time."""
    weight, _ = route(hin, m, w)
    mine = weight[:, w.first_expert:w.first_expert + w.held_experts]

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * _glu(hin, wg, wi, wo)

    return jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], mine.T)).sum(0)


def feed_forward(w: Widths, lp, hin):
    """hin [T, D] → the layer's second part [T, D]."""
    if "mlp" in lp:
        m = lp["mlp"]
        return _blocks(lambda hb: _glu(hb, m["wg"], m["wi"], m["wo"]), hin)
    return _blocks(lambda hb: experts_part(hb, lp["moe"], w), hin)


# -- the stack ---------------------------------------------------------------

@partial(jax.jit, static_argnames=("w", "kind"))
def _layer(x, lp, w: Widths, kind: str):
    """One layer on one sequence: x [T, D] float32 (T a multiple of the
    token block, or shorter) → x."""
    hin = dense._rms_norm(x, _f32(lp["ln1"]["scale"]), w.eps)
    if kind == "conv":
        x = x + conv_mixer(w, lp["conv"], hin)
    else:
        x = x + attention(w, lp["attn"], hin)
    return x + feed_forward(
        w, lp, dense._rms_norm(x, _f32(lp["ln2"]["scale"]), w.eps))


def _padded(tokens) -> np.ndarray:
    """Right-pad to a power of two of at least one token block (few shapes
    to compile; every part is causal, so the tail is harmless)."""
    out = np.zeros(dense._pow2_at_least(max(len(tokens), 1), TOKEN_BLOCK),
                   np.int32)
    out[:len(tokens)] = tokens
    return out


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence.
    Sequence-major: one sequence's stream is alive at a time."""
    emb = params["embed"]["tokens"]
    xs = []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device).astype(
                jnp.float32)
            for kind, lp in zip(w.kinds, params["layers"]):
                x = _layer(x, lp, w, kind)
            xs.append(x)
    return xs


@jax.jit
def _tied_head(x, scale, emb, eps):
    """``RMSNorm(x)·Eᵀ``: the embedding's rows are the head's columns."""
    return jnp.einsum("td,vd->tv", dense._rms_norm(x, _f32(scale), eps),
                      _f32(emb))


def _logits(w: Widths, params, x, device):
    with jax.default_matmul_precision("highest"):
        return np.asarray(_tied_head(
            x, jax.device_put(params["final_norm"]["scale"], device),
            jax.device_put(params["embed"]["tokens"], device), w.eps))


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' and the
    chip check's side of the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
    return _logits(w, params, x[:len(tokens)], device)


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last). No balance term."""
    rows = [np.asarray(r, np.int32) for r in batch]
    total = 0.0
    for r in rows:
        logits = jnp.asarray(logits_of(w, params, r, device))[:-1]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.asarray(r[1:])[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(nll))
    return total / sum(len(r) - 1 for r in rows)


def teacher_forced(w: Widths, params, prompts, outputs, device) -> dict:
    """Every generated token of every request, flattened: ``gap`` — how far
    the reference scores it below its own argmax at that position (0.0: it
    IS the argmax) — and ``lead``: how far that argmax leads the second
    best logit there. What :func:`argmax_gaps` judges from, and what
    ``tools/chip_control_lfm2.py`` tries other margins on."""
    gaps, leads = [], []
    for p, o in zip(prompts, outputs):
        (x,) = final_hidden(w, params, [_padded(list(p) + list(o))], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(dense._pow2_at_least(len(o), 64), np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        logits = _logits(w, params, x[at], device)[:len(o)]
        gaps.append(logits.max(axis=-1) -
                    logits[np.arange(len(o)), np.asarray(o)])
        top = np.sort(logits, axis=-1)[:, -2:]
        leads.append(top[:, 1] - top[:, 0])
    return {"gap": np.concatenate(gaps), "lead": np.concatenate(leads)}


def argmax_gaps(w: Widths, params, prompts, outputs, device) -> np.ndarray:
    """Teacher-forced check of generated tokens: for every generated token
    whose argmax this reference decides (``UNDECIDED_ARGMAX_MARGIN``;
    flattened over the requests), how far the reference scores it below
    its own argmax at that position (0.0: it IS the argmax)."""
    seen = teacher_forced(w, params, prompts, outputs, device)
    return seen["gap"][seen["lead"] >= UNDECIDED_ARGMAX_MARGIN]
