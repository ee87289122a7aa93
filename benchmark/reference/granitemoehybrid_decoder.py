"""The plain reference for GraniteMoeHybrid's stack (Granite 4.0-H Small,
``model_type: granitemoehybrid``), as its published ``config.json`` gives
it, on ONE CHIP'S SHARE of an expert-parallel deployment. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernels, no cache, no state pool, no batching, no dispatch — one
sequence at a time, a LAYER at a time (a layer's weights are cast to
float32 inside its own jitted call, one expert at a time, and freed with
it), a block of ``TOKEN_BLOCK`` tokens at a time where a part acts on a
token alone, every held expert computed for every token and weighed (0
where the token did not pick it), and the state-space mixer as the
PER-TOKEN recurrence under ``lax.scan`` (``nemotron_h_decoder.mamba_mixer``:
the same Mamba-2 mixer, here with ONE group).

EVERY layer ``l`` is a mixer AND the experts, under two RMSNorms
(``rms_norm_eps``), with four scalars ``e = embedding_multiplier``, ``a =
attention_multiplier``, ``r = residual_multiplier``, ``s =
logits_scaling``:

- ``x₀ = e · E[ids]``.
- ``h = RMSNorm(x)``. ``layer_types[l] == "mamba"``: the Mamba-2 mixer with
  ``H = mamba_n_heads`` heads of ``P = mamba_d_head``, ``d = H·P``
  (= ``mamba_expand`` x hidden), ``G = mamba_n_groups`` (1: ``B`` and ``C``
  shared by every head, the gated norm over the whole of ``d``), ``N =
  mamba_d_state``, ``K = mamba_d_conv``: ``[z | xBC | dt] = h·W_in``;
  ``u_t = silu(Σ_{i<K} w[:, i]·xBC_{t−K+1+i} + b)``; ``Δ_t = softplus(dt_t +
  dt_bias)`` (``time_step_limit`` (0, ∞): no clamp); ``S_t =
  exp(Δ_t·A)·S_{t−1} + Δ_t·x_t ⊗ B_t``; ``y_t = S_t·C_t + D·x_t``; ``o = w ⊙
  RMS(y ⊙ silu(z))``; ``o·W_out``. No bias but the convolution's.
  ``"attention"``: ``q = h·Wq → [T, Hq, Dh]``, ``k, v → [T, KV, Dh]``, NO
  positional term (``position_embedding_type: nope``); scores ``a · q_i·k_j``
  for ``0 ≤ i − j`` (``a`` = 1/128 at a head of 128: NOT ``1/√Dh``);
  softmax; ``(Σ_j p_ij v_j)·Wo``.
- ``x ← x + r · Mixer(h)``.
- ``h₂ = RMSNorm(x)``; router logits ``h₂·W_r`` (``num_local_experts``
  wide); the ``num_experts_per_tok`` largest kept; ``gate = softmax`` over
  THOSE logits alone; routed ``Σ_{e ∈ kept ∩ held} gate_e · W_out^e(silu(
  W_g^e h₂) ⊙ (W_u^e h₂))`` at ``intermediate_size``; plus ONE shared expert
  of the same form at ``shared_intermediate_size``, every token;
  ``x ← x + r · (Routed + Shared)``.
- final RMSNorm; ``logits = x·Eᵀ / s`` over the embedding's rows (a tied
  head).

**The share**: ``num_local_experts`` stays the router's published width;
``expert_share`` = ``{"first_expert", "held_experts"}`` (not a published
key) says which experts are held here. What the absent experts would add
is left out, here as in the program, and the partial result goes on to the
next layer. The vocabulary is the file's slice.

Departures from the published module (``transformers``'
``GraniteMoeHybridForCausalLM``; whatever could not be confirmed from the
catalog row stands under ``assumed`` in the configuration file): the scan
is per token, not in chunks of ``mamba_chunk_size`` (equal in exact
arithmetic); the experts' gate and up matrices are two trees ``wg`` / ``wi``
(the module fuses them into one ``input_linear``); the tree is the
program's (``[in, out]`` matrices; ``conv_w [d + 2GN, K]``).

**What ``argmax_gaps`` returns.** Gaps in units of the logits' OWN SPREAD
(:func:`spread_units`): a position's differences times
``UNSCALED_HEAD_SPREAD`` ÷ the standard deviation of the reference's logits
over the vocabulary at that position. The runner's near-tie limit of 0.25
was set for heads whose logits spread by ``0.02·√hidden`` ≈ 1.3 (cells
2–7); this model divides its logits by 16 and its program draws the tied
embedding at 0.02 ÷ 12 (``typed_layers.init_typed_params`` says why), so in
its own units the whole vocabulary lies within 0.03 of the maximum and the
limit would hold nothing. And, as ``nemotron_h_decoder.py`` does, only of
the tokens whose ROUTING this file's own margins decide, at the token's
position and at the ``mamba_d_conv − 1 + STATE_REACH`` positions before it
(:func:`held_margin`, :func:`decided`; the constants' docstring has the
readings).

It reads the program's typed layer tree (``params["layers"]`` is a LIST of
``{ln1, ssm | attn, ln2, moe, shared}``) and imports nothing from
``deepspeed_tpu``. It implements the reference contract stated at the top
of ``dense_decoder.py``; the padding helpers are that file's, the mixer and
the block helper ``nemotron_h_decoder.py``'s."""

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

#: a token's routing is DECIDED when, in every layer, no held expert could
#: change its membership of the kept set by a move of its router logit
#: smaller than ``UNDECIDED_LOGIT_MARGIN`` at the token's OWN position, than
#: ``NEIGHBOUR_LOGIT_MARGIN`` at the ``mamba_d_conv − 1`` positions before
#: it (which the convolution feeds it at full weight) and than
#: ``STATE_LOGIT_MARGIN`` at the ``STATE_REACH`` before those (the state's
#: fast heads). What a flip costs HERE, from the widths: the gate of the
#: last kept expert of ten is a softmax weight of ≈ 0.05, an expert's output
#: has an RMS of ≈ 0.14 and joins the stream times 0.22 — 0.4% of the
#: stream's RMS, ONE bf16 rounding's worth, where Nemotron-H's top-6 sigmoid
#: gate hands a flipped expert a weight of 0.4. So the margins are those of
#: what bf16 serving can flip at all (a router logit's own noise: ≈ 0.4% of
#: its spread of 1.3 ≈ 0.005), an order under Nemotron-H's, and they are
#: there to keep the judged tokens to those whose float32 walk the bf16
#: program can be held to, not to hide a large effect. Readings (PERF.md §6,
#: PR 45; v5e, gaps in :func:`spread_units`). ``tools/chip_check_granite_h``,
#: 512 positions of two rows in the 64-row bf16 programs: at an own margin of
#: 0 or 0.001 the worst gap under this file's argmax is 0.080, from 0.0025 on
#: it is 0.0 (302 / 265 / 212 / 138 positions judged at 0 / 0.0025 / 0.005 /
#: 0.01). The cell, under these constants: 1,287–1,950 tokens judged a run of
#: 8 requests (a third of them), 99.5–99.7% the reference's argmax, largest
#: gap 0.135; every weight matrix rounded to float8, the nearest precision
#: below, through the cell's own comparison
#: (``tools/chip_control_command_a.py``): 6.54, 76% exact — NOT ``correct``,
#: by the near-tie limit.
UNDECIDED_LOGIT_MARGIN = 0.005
NEIGHBOUR_LOGIT_MARGIN = 0.002
STATE_LOGIT_MARGIN = 0.0005
STATE_REACH = 3
#: the spread (standard deviation over the vocabulary) of an un-scaled head
#: on a unit-RMS hidden state at weights of std 0.02 and hidden 4096: what
#: the serve runner's ``NEAR_TIE_LOGITS`` was set against
UNSCALED_HEAD_SPREAD = 1.28


def spread_units(logits) -> np.ndarray:
    """[T, vocab] logits → [T]: the factor that turns a position's logit
    differences into units of ``UNSCALED_HEAD_SPREAD``: whatever scalars
    the model puts on its head, a difference is judged against how far the
    vocabulary's logits lie apart there."""
    return UNSCALED_HEAD_SPREAD / np.maximum(
        np.asarray(logits).std(axis=-1), 1e-30)


def neighbours_decided(margin: np.ndarray, w) -> np.ndarray:
    """[T] bool: the positions whose PREDECESSORS' routing is decided: the
    ``conv_kernel − 1`` before it by ``NEIGHBOUR_LOGIT_MARGIN``, the
    ``STATE_REACH`` before those by ``STATE_LOGIT_MARGIN`` (a position with
    fewer predecessors is held to those it has)."""
    margin = np.asarray(margin)
    ok = np.ones(len(margin), bool)
    for k in range(1, w.conv_kernel + STATE_REACH):
        ok[k:] &= margin[:-k] >= (NEIGHBOUR_LOGIT_MARGIN
                                  if k < w.conv_kernel
                                  else STATE_LOGIT_MARGIN)
    return ok


def decided(margin: np.ndarray, w) -> np.ndarray:
    """[T] bool from each position's :func:`held_margin` (least over the
    layers): the positions whose routing is decided, its own by
    ``UNDECIDED_LOGIT_MARGIN`` and its predecessors'."""
    return (np.asarray(margin) >= UNDECIDED_LOGIT_MARGIN) & \
        neighbours_decided(margin, w)


@dataclass(frozen=True)
class Widths:
    hidden: int
    layer_types: Tuple[str, ...]    # "mamba" or "attention", one a layer
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    conv_kernel: int
    eps: float
    expert_ffn: int
    shared_ffn: int
    router_experts: int
    first_expert: int
    held_experts: int
    per_token: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    vocab: int

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        layers = int(hf["num_hidden_layers"])
        types = tuple(hf["layer_types"][:layers])
        if len(types) != layers or set(types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {types!r} for {layers} layers: "
                             f"'mamba' and 'attention'")
        experts = int(hf["num_local_experts"])
        share = hf.get("expert_share") or {"first_expert": 0,
                                           "held_experts": experts}
        heads = int(hf["num_attention_heads"])
        return cls(
            hidden=int(hf["hidden_size"]), layer_types=types, heads=heads,
            kv_heads=int(hf["num_key_value_heads"]),
            head_dim=int(hf["hidden_size"]) // heads,
            ssm_heads=int(hf["mamba_n_heads"]),
            ssm_head_dim=int(hf["mamba_d_head"]),
            ssm_groups=int(hf["mamba_n_groups"]),
            ssm_state=int(hf["mamba_d_state"]),
            conv_kernel=int(hf["mamba_d_conv"]),
            eps=float(hf["rms_norm_eps"]),
            expert_ffn=int(hf["intermediate_size"]),
            shared_ffn=int(hf["shared_intermediate_size"]),
            router_experts=experts,
            first_expert=int(share["first_expert"]),
            held_experts=int(share["held_experts"]),
            per_token=int(hf["num_experts_per_tok"]),
            embedding_multiplier=float(hf["embedding_multiplier"]),
            attention_multiplier=float(hf["attention_multiplier"]),
            residual_multiplier=float(hf["residual_multiplier"]),
            logits_scaling=float(hf["logits_scaling"]),
            vocab=int(hf["vocab_size"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies ON THIS CHIP, forward: a ``mamba`` layer's
    two projections or an ``attention`` layer's four; in EVERY layer the
    router at its full width, the shared expert and, of the token's
    ``per_token`` experts, the share that is held here (three matrices
    each); the tied head over the vocabulary slice. (The scan's own sums
    are not matmul parameters.)"""
    qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
    mixer = {"mamba": w.hidden * (2 * w.inner + 2 * w.ssm_groups *
                                  w.ssm_state + w.ssm_heads)
             + w.inner * w.hidden,
             "attention": 2 * w.hidden * qd + 2 * w.hidden * kd}
    experts = w.hidden * w.router_experts + 3 * w.hidden * w.shared_ffn + \
        round(w.per_token * w.held_experts / w.router_experts
              * 3 * w.hidden * w.expert_ffn)
    return int(sum(mixer[name] + experts for name in w.layer_types)
               + w.hidden * w.vocab)


# -- attention with no positional term and a stated scale ---------------------

def attention(w: Widths, p, hin):
    """hin [T, D] → [T, D]; causal, grouped-query, no positions, scores
    times ``attention_multiplier``."""
    t = hin.shape[0]
    k = (hin @ _f32(p["wk"])).reshape(t, w.kv_heads, w.head_dim)
    v = (hin @ _f32(p["wv"])).reshape(t, w.kv_heads, w.head_dim)
    per = w.heads // w.kv_heads
    blk = min(t, TOKEN_BLOCK)

    def block(hb, qpos):
        q = (hb @ _f32(p["wq"])).reshape(blk, w.kv_heads, per, w.head_dim)
        s = jnp.einsum("qgpd,kgd->gpqk", q, k) * w.attention_multiplier
        ok = qpos[:, None] >= jnp.arange(t)[None]
        pr = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gpqk,kgd->qgpd", pr, v)
        return o.reshape(blk, w.heads * w.head_dim) @ _f32(p["wo"])

    return _blocks(block, hin, jnp.arange(t))


# -- the experts --------------------------------------------------------------

def route(hin, m, w: Widths):
    """hin [T, D] → the gate of every one of the router's experts for every
    token [T, router_experts] (0 where not kept), and the kept ids [T,
    per_token], best first: the softmax over the KEPT logits alone."""
    logits = hin @ _f32(m["router"])
    kept, sel = jax.lax.top_k(logits, w.per_token)
    gate = jax.nn.softmax(kept, axis=-1)
    chosen = jax.nn.one_hot(sel, w.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", gate, chosen), sel


def held_margin(hin, m, w: Widths):
    """hin [T, D] → [T]: the least move of ONE held expert's router logit
    that changes whether it is kept: a kept expert leaves when its logit
    falls to the best one left out, one left out enters when its logit
    rises to the last kept. Experts held elsewhere are not counted: both
    sides drop their part."""
    logits = hin @ _f32(m["router"])
    top = jax.lax.top_k(logits, w.per_token + 1)[0]
    last_in = top[:, w.per_token - 1:w.per_token]
    best_out = top[:, w.per_token:]
    mine = logits[:, w.first_expert:w.first_expert + w.held_experts]
    return jnp.min(jnp.where(mine >= last_in, mine - best_out,
                             last_in - mine), axis=-1)


def _glu_unit(hin, wg, wi, wo):
    return (jax.nn.silu(hin @ _f32(wg)) * (hin @ _f32(wi))) @ _f32(wo)


def experts_part(hin, m, w: Widths):
    """The part of the routed sum that the HELD experts give: hin [T, D] →
    [T, D]. With every expert held it is the whole routed sum. One expert's
    weights are cast to float32 at a time."""
    gate, _ = route(hin, m, w)
    mine = gate[:, w.first_expert:w.first_expert + w.held_experts]

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * _glu_unit(hin, wg, wi, wo)

    return jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], mine.T)).sum(0)


def experts_layer(w: Widths, lp, hin):
    """hin [T, D] → (routed part + shared expert [T, D], margins [T])."""
    def block(hb):
        sh = lp["shared"]
        return experts_part(hb, lp["moe"], w) + _glu_unit(
            hb, sh["wg"], sh["wi"], sh["wo"]), held_margin(hb, lp["moe"], w)

    return _blocks(block, hin)


# -- the stack ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("w", "name"))
def _layer(x, lp, w: Widths, name: str):
    """One layer on one sequence: x [T, D] float32 (T a multiple of the
    token block, or shorter) → (x, the layer's :func:`held_margin` [T])."""
    hin = dense._rms_norm(x, _f32(lp["ln1"]["scale"]), w.eps)
    mixed = hybrid.mamba_mixer(w, lp["ssm"], hin) if name == "mamba" \
        else attention(w, lp["attn"], hin)
    x = x + w.residual_multiplier * mixed
    out, margin = experts_layer(
        w, lp, dense._rms_norm(x, _f32(lp["ln2"]["scale"]), w.eps))
    return x + w.residual_multiplier * out, margin


def _padded(tokens) -> np.ndarray:
    """Right-pad to a power of two of at least one token block (few shapes
    to compile; every part is causal, so the tail is harmless)."""
    out = np.zeros(dense._pow2_at_least(max(len(tokens), 1), TOKEN_BLOCK),
                   np.int32)
    out[:len(tokens)] = tokens
    return out


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the last layer's hidden states [T, D] float32, and
    each position's least :func:`held_margin` over the layers [T].
    Sequence-major and a layer at a time: one sequence's stream and one
    layer's float32 weights are alive at a time."""
    emb = params["embed"]["tokens"]
    xs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device).astype(
                jnp.float32) * w.embedding_multiplier
            margin = jnp.full(len(r), jnp.inf, jnp.float32)
            for name, lp in zip(w.layer_types, params["layers"]):
                x, m = _layer(x, lp, w, name)
                margin = jnp.minimum(margin, m)
            xs.append(x)
            margins.append(margin)
    return xs, margins


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence."""
    return hidden_and_margins(w, params, token_rows, device)[0]


def _head_of(params, device):
    """(final norm's scale, the tied head ``Eᵀ`` [D, vocab]) in float32."""
    return dense._f32(params["final_norm"]["scale"], device), \
        dense._f32(params["embed"]["tokens"], device).T


def _logits(w: Widths, x, scale, head):
    return dense._head(x, scale, head, w.eps) / w.logits_scaling


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' and the
    chip check's side of the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
    scale, head = _head_of(params, device)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_logits(w, x[:len(tokens)], scale, head))


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


def argmax_gaps(w: Widths, params, prompts, outputs, device) -> np.ndarray:
    """Teacher-forced check of generated tokens: for every generated token
    whose routing is decided (the module docstring; flattened over the
    requests), how far the reference scores it below its own argmax at
    that position, in :func:`spread_units` (0.0: it IS the argmax)."""
    scale, head = _head_of(params, device)
    gaps = []
    for p, o in zip(prompts, outputs):
        (x,), (margin,) = hidden_and_margins(
            w, params, [_padded(list(p) + list(o))], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(dense._pow2_at_least(len(o), 64), np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(_logits(w, x[at], scale, head))[:len(o)]
        judged = decided(margin, w)[at[:len(o)]]
        gap = logits.max(axis=-1) - logits[np.arange(len(o)), np.asarray(o)]
        gaps.append((gap * spread_units(logits))[judged])
    return np.concatenate(gaps)
