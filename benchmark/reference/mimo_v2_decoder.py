"""The plain reference for MiMo-V2 / V2.5's language model
(``model_type: mimo_v2``), as its published ``config.json`` describes it,
on ONE CHIP'S SHARE of an expert-parallel deployment. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
no kernels, no cache, no batching, no dispatch — one sequence at a time,
one layer's weights cast to float32 at a time, every held expert computed
for every token and weighed (0 where the token did not pick it).

For layer ``l`` of kind ``a = hybrid_layer_pattern[l]`` (0 full, 1 window):

- ``h = RMSNorm(x; layernorm_epsilon)``; ``q = h·Wq → [T, H, 192]``,
  ``k = h·Wk → [T, KV_a, 192]``, ``v = attention_value_scale · (h·Wv) →
  [T, KV_a, 128]``; no biases; ``KV_0 = num_key_value_heads``, ``KV_1 =
  swa_num_key_value_heads``. Rotate-half RoPE on the first
  ``int(head_dim · partial_rotary_factor)`` (made even) dims of every q
  and k head, ``inv_freq = θ_a ** (−2i / rope_dim)``, ``θ_0 = rope_theta``,
  ``θ_1 = swa_rope_theta``; the other dims pass through.
- scores ``s_ij = q_i·k_j / √head_dim`` for ``0 ≤ i − j`` and, on window
  layers, ``i − j < sliding_window`` (self included, as
  ``dense_decoder.py``). With ``add_swa_attention_sink_bias`` a window
  layer has a learned ``sink[h]``: one more softmax column that takes mass
  and gives no value. ``o = Σ_j p_ij v_j``; ``x ← x + o·Wo``.
- ``h2 = RMSNorm(x)``. ``moe_layer_freq[l] = 0``: ``x ← x + (silu(h2·Wg) ⊙
  (h2·Wi))·Wo`` at ``intermediate_size``. Else ``z = sigmoid(h2·Wr)`` over
  ALL the router's experts; ``S`` = the ``num_experts_per_tok`` largest of
  ``z + b`` (``topk_method: noaux_tc``: the bias picks and does not
  weigh); ``w_e = z_e / (Σ_{e'∈S} z_e' + 1e-20)`` (``norm_topk_prob``; no
  ``routed_scaling_factor``, one group, no shared expert); ``x ← x +
  Σ_{e ∈ S ∩ held} w_e · (silu(h2·Wg_e) ⊙ (h2·Wi_e))·Wo_e`` at
  ``moe_intermediate_size``. **The share**: the file's ``n_routed_experts``
  counts the experts HELD here, ``expert_share`` = ``{"router_experts",
  "first_expert"}`` gives the router's published width and the first
  expert held; what the absent experts would add is left out, here as in
  the program, and the partial result goes on to the next layer.
- final RMSNorm; ``logits = x·W_head`` (untied, no scaling).

Assumed (the config names them, its code was not at hand): that
``attention_value_scale`` multiplies V; that the sink enters as one extra
softmax column per head; that ``attention_chunk_size``,
``hybrid_block_size`` and ``attention_projection_layout`` do not change
the mathematics. Not built: the vision and audio towers and the
multi-token-prediction layers (no key of the language model's config).

**What ``argmax_gaps`` judges.** A top-8-of-256 selection is a discontinuity:
where a HELD expert's pick stands within a hair of the selection boundary,
bf16 serving and this float32 walk may land on either side of it, each
soundly, and a whole expert's output then parts their logits (on the v5e
the program's eight differ from these in 10.7% of (token, layer)
selections, in a held expert in 1.3%, and so does a float32 router over a
bf16 stream; PERF.md §6, PR 31). Such a token says nothing about the
program, so ``argmax_gaps`` returns the gaps of the tokens whose routing
this file's own margins DECIDE (:func:`held_margin`,
``UNDECIDED_LOGIT_MARGIN``) and leaves the others out — blind to what the
program chose there; the runner's limits are then held over every token
returned.

It reads the program's typed layer tree (``params["layers"]`` is a LIST;
``attn`` {wq, wk, wv, wo, sink?}, ``mlp`` or ``moe`` {router,
router_bias?, wg, wi, wo over the held experts}), and imports nothing from
``deepspeed_tpu``. It implements the reference contract stated at the top
of ``dense_decoder.py``; the embedding, head and padding helpers are that
file's."""

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense

QUERY_BLOCK = dense.QUERY_BLOCK

#: a token's routing is DECIDED when, in every sparse layer, no held expert
#: could change its membership of the selected set by a move of its router
#: logit smaller than this. Between two readings on the v5e (PERF.md §6, PR
#: 31; 7,168 generated tokens a reading): where the sound bf16 program's held
#: experts differ from this file's, the margin reads 0.029-0.034 at the 90th
#: percentile (median 0.010); where the program with its matmul inputs
#: rounded to float8 (e4m3) differs, 0.078 at the MEDIAN (0.267 at the 95th
#: percentile), and that program is not correct by the runner's limits over
#: the decided tokens (largest gap 0.607 of 0.25, 0.707 through the runner
#: itself; the sound program's 0.065-0.096, 0.030-0.082 over 22 cell runs). At 0.04, 33-34% of tokens are left out and 7-9 of the 4,750
#: judged still carry a held flip (515-536 of the 7,168 before). A bf16
#: ROUTER cannot be told from the sound program by any limit (11.1% of
#: selections differ against 10.7%): the bf16 stream's own rounding hides it.
UNDECIDED_LOGIT_MARGIN = 0.04


@dataclass(frozen=True)
class Widths:
    hidden: int
    heads: int
    kv_heads: Tuple[int, int]       # by kind: (full, window)
    head_dim: int
    v_head_dim: int
    rope_dim: int
    theta: Tuple[float, float]      # by kind
    window: int
    sink: bool                      # on window layers
    value_scale: float
    eps: float
    layers: int
    kinds: Tuple[int, ...]          # 0 full, 1 window
    sparse: Tuple[int, ...]         # 0 dense, 1 experts
    dense_ffn: int
    expert_ffn: int
    router_experts: int
    first_expert: int
    held_experts: int
    per_token: int
    norm_topk: bool
    vocab: int

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        layers = int(hf["num_hidden_layers"])
        dk = int(hf["head_dim"])
        rope = int(dk * float(hf.get("partial_rotary_factor", 1.0)))
        held = int(hf["n_routed_experts"])
        share = hf.get("expert_share") or {"router_experts": held,
                                           "first_expert": 0}
        return cls(
            hidden=int(hf["hidden_size"]),
            heads=int(hf["num_attention_heads"]),
            kv_heads=(int(hf["num_key_value_heads"]),
                      int(hf["swa_num_key_value_heads"])),
            head_dim=dk, v_head_dim=int(hf["v_head_dim"]),
            rope_dim=rope - rope % 2,
            theta=(float(hf["rope_theta"]), float(hf["swa_rope_theta"])),
            window=int(hf["sliding_window"]),
            sink=bool(hf["add_swa_attention_sink_bias"]),
            value_scale=float(hf["attention_value_scale"]),
            eps=float(hf["layernorm_epsilon"]), layers=layers,
            kinds=tuple(int(a) for a in hf["hybrid_layer_pattern"][:layers]),
            sparse=tuple(int(a) for a in hf["moe_layer_freq"][:layers]),
            dense_ffn=int(hf["intermediate_size"]),
            expert_ffn=int(hf["moe_intermediate_size"]),
            router_experts=int(share["router_experts"]),
            first_expert=int(share["first_expert"]), held_experts=held,
            per_token=int(hf["num_experts_per_tok"]),
            norm_topk=bool(hf["norm_topk_prob"]),
            vocab=int(hf["vocab_size"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies ON THIS CHIP, forward: each layer's
    attention projections at its kind's KV heads; the dense layer's GLU;
    in a sparse layer the router at its full width and, of the token's
    ``per_token`` experts, the share that is held here (``per_token x
    held / router_experts`` of them on average, three matrices each); the
    untied head over the vocabulary slice."""
    total = w.hidden * w.vocab
    for kind, sparse in zip(w.kinds, w.sparse):
        kvh = w.kv_heads[kind]
        total += w.hidden * w.heads * w.head_dim \
            + w.hidden * kvh * (w.head_dim + w.v_head_dim) \
            + w.heads * w.v_head_dim * w.hidden
        if sparse:
            total += w.hidden * w.router_experts + round(
                w.per_token * w.held_experts / w.router_experts
                * 3 * w.hidden * w.expert_ffn)
        else:
            total += 3 * w.hidden * w.dense_ffn
    return int(total)


def _rope(x, positions, theta: float, rope_dim: int):
    """x [T, H, Dk]: rotate-half on dims [0, rope_dim), the rest pass."""
    rot, rest = x[..., :rope_dim], x[..., rope_dim:]
    inv_freq = theta ** (-jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                         / rope_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(rot, 2, axis=-1)
    rot = rot * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([rot, rest], -1)


def _attention(q, k, v, window, sink):
    """q [T, H, Dk], k [T, KvH, Dk], v [T, KvH, Dv] → [T, H, Dv]; T a
    multiple of the query block. ``window`` None: full causal. ``sink``
    [H] or None: one more softmax column, no value."""
    t, h, dk = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    blk = min(t, QUERY_BLOCK)
    kpos = jnp.arange(t)

    def block(args):
        qb, start = args
        qpos = start + jnp.arange(blk)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (dk ** -0.5)
        dist = qpos[:, None] - kpos[None, :]
        ok = dist >= 0
        if window is not None:
            ok = ok & (dist < window)
        s = jnp.where(ok[None], s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate(
                [s, jnp.broadcast_to(sink[:, None, None], (h, blk, 1))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :t]
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(t // blk, blk, h, dk),
                              jnp.arange(0, t, blk)))
    return out.reshape(t, h, v.shape[-1])


def attention_block(x, lp, w: Widths, kind: int):
    """x [T, D] float32 → x + attention(RMSNorm(x)) of a layer of
    ``kind``."""
    t = x.shape[0]
    pos = jnp.arange(t)
    a, kvh = lp["attn"], w.kv_heads[kind]
    hin = dense._rms_norm(x, lp["ln1"]["scale"], w.eps)
    q = (hin @ a["wq"]).reshape(t, w.heads, w.head_dim)
    k = (hin @ a["wk"]).reshape(t, kvh, w.head_dim)
    v = w.value_scale * (hin @ a["wv"]).reshape(t, kvh, w.v_head_dim)
    q = _rope(q, pos, w.theta[kind], w.rope_dim)
    k = _rope(k, pos, w.theta[kind], w.rope_dim)
    o = _attention(q, k, v, w.window if kind else None,
                   a["sink"] if kind and w.sink else None)
    return x + o.reshape(t, w.heads * w.v_head_dim) @ a["wo"]


def _scores(hin, m):
    """hin [T, D] → (router logits, scores z = sigmoid(logits), picks z + b
    that the selection compares), each [T, router_experts] float32."""
    logits = hin @ m["router"]
    z = jax.nn.sigmoid(logits)
    return logits, z, (z + m["router_bias"] if "router_bias" in m else z)


def route(hin, m, w: Widths):
    """hin [T, D] → the weight of every one of the router's experts for
    every token, [T, router_experts] float32 (0 where not selected), and
    the selected ids [T, per_token]."""
    _, z, pick = _scores(hin, m)
    _, sel = jax.lax.top_k(pick, w.per_token)
    kept = jnp.take_along_axis(z, sel, axis=-1)
    if w.norm_topk:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(sel, w.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", kept, chosen), sel


def held_margin(hin, m, w: Widths):
    """hin [T, D] → [T] float32: the least move of ONE held expert's router
    logit that changes whether it is selected. A selected expert ``e``
    leaves when its pick ``sigmoid(l_e) + b_e`` falls to the best
    unselected pick; an unselected one enters when its pick rises to the
    last selected pick: in logits, ``|l_e − logit(that pick − b_e)|`` (a
    pick no score can reach: no move does it). Experts held elsewhere are
    not counted: both sides drop their part."""
    logits, _, pick = _scores(hin, m)
    top = jax.lax.top_k(pick, w.per_token + 1)[0]
    last_in = top[:, w.per_token - 1:w.per_token]
    best_out = top[:, w.per_token:]
    held = slice(w.first_expert, w.first_expert + w.held_experts)
    bias = m["router_bias"][held] if "router_bias" in m else 0.0
    selected = pick[:, held] >= last_in
    target = jnp.where(selected, best_out, last_in) - bias     # a score
    reachable = (target > 0.0) & (target < 1.0)
    safe = jnp.where(reachable, target, 0.5)
    move = jnp.abs(logits[:, held] - (jnp.log(safe) - jnp.log1p(-safe)))
    return jnp.min(jnp.where(reachable, move, jnp.inf), axis=-1)


def experts_part(hin, m, w: Widths):
    """The part of the sparse layer's output that the HELD experts give:
    hin [T, D] (the normed input) → [T, D]. With every expert held it is
    the whole layer's."""
    weight, _ = route(hin, m, w)
    mine = weight[:, w.first_expert:w.first_expert + w.held_experts]

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * ((jax.nn.silu(hin @ wg) * (hin @ wi)) @ wo)

    return jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], mine.T)).sum(0)


@partial(jax.jit, static_argnames=("w", "kind", "sparse"))
def _layer(x, lp, w: Widths, kind: int, sparse: int):
    """One layer on one sequence. x [T, D] float32 → (x, the layer's
    :func:`held_margin` [T]; +inf for a dense layer)."""
    x = attention_block(x, lp, w, kind)
    hin = dense._rms_norm(x, lp["ln2"]["scale"], w.eps)
    if sparse:
        return x + experts_part(hin, lp["moe"], w), \
            held_margin(hin, lp["moe"], w)
    m = lp["mlp"]
    return x + (jax.nn.silu(hin @ m["wg"]) * (hin @ m["wi"])) @ m["wo"], \
        jnp.full(x.shape[:1], jnp.inf, jnp.float32)


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the last layer's hidden states [T, D] float32, and
    each position's least :func:`held_margin` over the sparse layers [T].
    This file's own walk over the LIST of typed layers, layer-major (each
    layer's weights are cast once and used for every sequence)."""
    emb = params["embed"]["tokens"]
    xs = [jax.device_put(emb[jnp.asarray(r)], device).astype(jnp.float32)
          for r in token_rows]
    margins = [jnp.full(len(r), jnp.inf, jnp.float32) for r in token_rows]
    with jax.default_matmul_precision("highest"):
        for i, (kind, sparse) in enumerate(zip(w.kinds, w.sparse)):
            lp = dense._f32(params["layers"][i], device)
            for j, x in enumerate(xs):
                xs[j], m = _layer(x, lp, w, kind, sparse)
                margins[j] = jnp.minimum(margins[j], m)
            del lp
    return xs, margins


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence."""
    return hidden_and_margins(w, params, token_rows, device)[0]


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' side of
    the comparison; T is padded to the query block and cut back)."""
    row = dense._padded(list(tokens))
    (x,) = final_hidden(w, params, [row], device)
    with jax.default_matmul_precision("highest"):
        out = dense._head(x, dense._f32(params["final_norm"]["scale"],
                                        device),
                          dense._f32(params["lm_head"], device), w.eps)
    return np.asarray(out)[:len(tokens)]


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last). No balance term: the published gate has none
    (``noaux_tc``)."""
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
    that position (0.0: it IS the argmax). A model with no sparse layer
    has no undecided token."""
    rows = [dense._padded(list(p) + list(o))
            for p, o in zip(prompts, outputs)]
    xs, margins = hidden_and_margins(w, params, rows, device)
    scale = dense._f32(params["final_norm"]["scale"], device)
    head = dense._f32(params["lm_head"], device)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, o, x, margin in zip(prompts, outputs, xs, margins):
            # logits at position len(p)-1+j predict generated token j
            at = np.zeros(dense._pow2_at_least(len(o), 64), np.int32)
            at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
            logits = np.asarray(dense._head(x[at], scale, head,
                                            w.eps))[:len(o)]
            decided = np.asarray(margin)[at[:len(o)]] >= \
                UNDECIDED_LOGIT_MARGIN
            gaps.append((logits.max(axis=-1) -
                         logits[np.arange(len(o)), np.asarray(o)])[decided])
    return np.concatenate(gaps)
