"""The plain reference for Cohere2-MoE's language model (Command A+,
``model_type: cohere2_moe``), as its published ``config.json`` and the
catalog's description give it, on ONE CHIP'S SHARE of an expert-parallel
deployment. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no dispatch — one sequence at a time, a block of
``QUERY_BLOCK`` queries at a time (so a request of 11K tokens fits beside a
serving engine's weights and arena), every held expert computed for every
token and weighed (0 where the token did not pick it).

For layer ``l`` with ``layer_types[l]`` ∈ {``sliding_attention``,
``full_attention``}:

- ``h = LayerNorm(x)``: ``(x − mean)·rsqrt(var + layer_norm_eps)·scale``,
  no bias (the published ``rms_norm_eps`` is null; ``layer_norm_eps`` is
  the one read).
- ``q = h·Wq → [T, H, Dh]``, ``k = h·Wk → [T, KV, Dh]``, ``v = h·Wv →
  [T, KV, Dh]``; no bias, no QK norm. WINDOW layers: rotary on the first
  ``rotary_pct`` of the head in INTERLEAVED pairs ``(2i, 2i + 1)``
  (``position_embedding_type: rope_gptj``), ``inv_freq = θ ** (−2i /
  rope_dim)``. FULL layers: NO positional term.
- scores ``q_i·k_j / √Dh`` for ``0 ≤ i − j`` and, on window layers,
  ``i − j < sliding_window`` (self included, as ``dense_decoder.py``);
  softmax; ``a = (Σ_j p_ij v_j)·Wo``; query head ``h`` reads KV head
  ``h // (H / KV)``.
- ``z = sigmoid(h·Wr)`` over ALL ``num_experts``; ``S`` = the
  ``num_experts_per_tok`` largest ``z`` (no selection bias); ``w_e = z_e /
  (Σ_{e'∈S} z_e' + 1e-20)`` (``norm_topk_prob``); routed ``r = Σ_{e ∈ S ∩
  held} w_e·(silu(h·Wg_e) ⊙ h·Wi_e)·Wo_e`` at ``intermediate_size``;
  shared ``s = (1/n) Σ_{i<n} (silu(h·Wg_i) ⊙ h·Wi_i)·Wo_i`` with ``n =
  num_shared_experts`` (the tree holds them side by side: one GLU of
  ``n × intermediate_size``, so its output is their SUM).
- ``x ← x + a + r + s`` (``use_parallel_block``: attention and experts
  read the SAME ``h``; nothing re-normalises the stream between them).
- final LayerNorm; ``logits = x·Eᵀ`` over the embedding's rows (tied;
  ``logit_scale`` 1).

**The share**: ``num_experts`` is the ROUTER's width as published;
``expert_share`` = ``{"router_experts", "first_expert", "held_experts"}``
(not a published key) names the experts HELD here. What the absent experts
would add is left out, here as in the program, and the partial result goes
on to the next layer.

Assumed (the config names them, its code was not at hand):
``shared_expert_combination_strategy: average`` is the MEAN of the shared
experts' outputs, added beside the routed sum (the other reading, a mean
over shared AND routed outputs together, is not taken); the full layers
carry no positional term (the catalog: "global NoPE"; Cohere2's published
module does the same); ``sliding_window`` includes the query itself. Not
built: the vision tower; ``prefix_dense_*`` (no layer uses them at
``first_k_dense_replace`` 0).

**What ``argmax_gaps`` judges**: as ``mimo_v2_decoder.py`` — a top-8-of-128
selection is a discontinuity, so it returns the gaps of the tokens whose
routing this file's own margins DECIDE (:func:`held_margin`,
``UNDECIDED_LOGIT_MARGIN``) and leaves the others out.

It reads the program's typed layer tree (``params["layers"]`` is a LIST;
``ln1``, ``attn`` {wq, wk, wv, wo}, ``moe`` {router, wg, wi, wo over the
held experts}, ``shared`` {wg, wi, wo}), and imports nothing from
``deepspeed_tpu``. It implements the reference contract stated at the top
of ``dense_decoder.py``; the padding helpers are that file's."""

from dataclasses import dataclass
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense

#: queries a block of the layer walk holds at once (16 query heads x 256
#: queries x 12,288 keys of float32 scores a KV head: 0.2 GB)
QUERY_BLOCK = 256
#: sequences are padded to a multiple of this (few shapes to compile; a
#: power of two would pad an 11,008-token request to 16,384)
PAD_TO = 2048

#: a token's routing is DECIDED when, in every layer, no held expert could
#: change its membership of the selected set by a move of its router logit
#: smaller than this (``mimo_v2_decoder.py`` has the argument). Between two
#: readings on the v5e (PERF.md §6, PR 37). The sound bf16 program, under
#: this file's argmax: 0.000-0.027 over the runs of the cell (the runner's
#: limit is 0.25), 0.003 over every position of
#: ``tools/chip_check_command_a.py``'s 256 after a 6,016-token prompt (its
#: largest LOGIT difference 0.679 over every position, a flipped held
#: expert, and 0.047 from a margin of 0.01 up). The program with every
#: weight matrix rounded to float8 (e4m3), the nearest precision below the
#: configuration's, THROUGH THE RUNNER'S OWN ``correct`` on the cell (two
#: seeds, 1,050 and 788 judged tokens): 0.511 and 1.015 at 0.04 — caught by
#: ``tokens_within_near_tie``, exact share 98.6% and 90.0% (over the 75%
#: floor: that check does not catch it); by margin, the first seed reads
#: 0.570, 0.570, 0.542, 0.511, 0.484 at 0.0-0.08 and 0.000 at 0.16 (111
#: tokens: NOT caught), the second 1.01-1.18 at every margin. The tool's
#: one walk read 0.305 to 0.04 and 0.077 from 0.08 up. At 0.04 a run
#: judges 474-1,501 tokens, 65% of those generated. What this comparison
#: does NOT catch is a lower-precision KV CACHE: K and V rounded to float8
#: on their way to the pool read 0.039 and 99.1% exact through the runner
#: (``correct`` true); only the tool's limit on the LOGITS (0.1; 0.384
#: there) holds the cache's precision.
UNDECIDED_LOGIT_MARGIN = 0.04


@dataclass(frozen=True)
class Widths:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_dim: int
    theta: float
    window: int
    eps: float
    layers: int
    kinds: Tuple[int, ...]          # 0 full (no positions), 1 window
    expert_ffn: int
    shared_experts: int
    router_experts: int
    first_expert: int
    held_experts: int
    per_token: int
    norm_topk: bool
    vocab: int

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        layers = int(hf["num_hidden_layers"])
        dh = int(hf["head_dim"])
        rope = int(dh * float(hf.get("rotary_pct", 1.0)))
        experts = int(hf["num_experts"])
        share = hf.get("expert_share") or {
            "router_experts": experts, "first_expert": 0,
            "held_experts": experts}
        names = {"full_attention": 0, "sliding_attention": 1}
        return cls(
            hidden=int(hf["hidden_size"]),
            heads=int(hf["num_attention_heads"]),
            kv_heads=int(hf["num_key_value_heads"]),
            head_dim=dh, rope_dim=rope - rope % 2,
            theta=float(hf["rope_theta"]),
            window=int(hf["sliding_window"]),
            eps=float(hf["layer_norm_eps"]), layers=layers,
            kinds=tuple(names[n] for n in hf["layer_types"][:layers]),
            expert_ffn=int(hf["intermediate_size"]),
            shared_experts=int(hf["num_shared_experts"]),
            router_experts=int(share["router_experts"]),
            first_expert=int(share["first_expert"]),
            held_experts=int(share["held_experts"]),
            per_token=int(hf["num_experts_per_tok"]),
            norm_topk=bool(hf["norm_topk_prob"]),
            vocab=int(hf["vocab_size"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies ON THIS CHIP, forward: each layer's four
    attention projections, the router at its full width, the shared
    experts, and of the token's ``per_token`` experts the share that is
    held here (``per_token x held / router_experts`` of them on average,
    three matrices each); the tied head over the vocabulary slice."""
    qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
    layer = 2 * w.hidden * qd + 2 * w.hidden * kd \
        + w.hidden * w.router_experts \
        + w.shared_experts * 3 * w.hidden * w.expert_ffn \
        + round(w.per_token * w.held_experts / w.router_experts
                * 3 * w.hidden * w.expert_ffn)
    return int(w.layers * layer + w.hidden * w.vocab)


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale


def _rope_interleaved(x, positions, theta: float, rope_dim: int):
    """x [T, H, Dh]: pair ``i`` is dims ``(2i, 2i + 1)`` of the first
    ``rope_dim``; the rest pass."""
    rot, rest = x[..., :rope_dim], x[..., rope_dim:]
    inv_freq = theta ** (-jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                         / rope_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1).reshape(rot.shape)
    return jnp.concatenate([out, rest], -1)


def _scores(hin, m):
    """hin [T, D] → (router logits, scores z = sigmoid(logits)), each
    [T, router_experts] float32. No selection bias: the picks ARE z."""
    logits = hin @ m["router"].astype(jnp.float32)
    return logits, jax.nn.sigmoid(logits)


def route(hin, m, w: Widths):
    """hin [T, D] → the weight of every one of the router's experts for
    every token, [T, router_experts] float32 (0 where not selected), and
    the selected ids [T, per_token]."""
    _, z = _scores(hin, m)
    kept, sel = jax.lax.top_k(z, w.per_token)
    if w.norm_topk:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(sel, w.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", kept, chosen), sel


def held_margin(hin, m, w: Widths):
    """hin [T, D] → [T] float32: the least move of ONE held expert's router
    logit that changes whether it is selected (a selected expert leaves
    when its score falls to the best unselected score; an unselected one
    enters when its score rises to the last selected one): in logits,
    ``|l_e − logit(that score)|``. Experts held elsewhere are not counted:
    both sides drop their part."""
    logits, z = _scores(hin, m)
    top = jax.lax.top_k(z, w.per_token + 1)[0]
    last_in = top[:, w.per_token - 1:w.per_token]
    best_out = top[:, w.per_token:]
    held = slice(w.first_expert, w.first_expert + w.held_experts)
    selected = z[:, held] >= last_in
    target = jnp.where(selected, best_out, last_in)            # a score
    reachable = (target > 0.0) & (target < 1.0)
    safe = jnp.where(reachable, target, 0.5)
    move = jnp.abs(logits[:, held] - (jnp.log(safe) - jnp.log1p(-safe)))
    return jnp.min(jnp.where(reachable, move, jnp.inf), axis=-1)


def _glu(hin, wg, wi, wo):
    f32 = jnp.float32
    return (jax.nn.silu(hin @ wg.astype(f32)) * (hin @ wi.astype(f32))) \
        @ wo.astype(f32)


def experts_part(hin, m, w: Widths):
    """The part of the routed sum that the HELD experts give: hin [T, D]
    (the normed input) → [T, D]. With every expert held it is the whole
    routed sum. One expert's weights are cast to float32 at a time."""
    weight, _ = route(hin, m, w)
    mine = weight[:, w.first_expert:w.first_expert + w.held_experts]

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * _glu(hin, wg, wi, wo)

    return jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], mine.T)).sum(0)


def shared_part(hin, sh, w: Widths):
    """The shared experts' AVERAGE. The tree holds them side by side (one
    GLU of ``n x width``: columns ``[i·f, (i+1)·f)`` of ``wg`` / ``wi`` and
    the same rows of ``wo`` are expert ``i``); here one at a time."""
    n, f, d = w.shared_experts, w.expert_ffn, w.hidden

    def expert(args):
        return _glu(hin, *args)

    return jax.lax.map(expert, (
        sh["wg"].reshape(d, n, f).transpose(1, 0, 2),
        sh["wi"].reshape(d, n, f).transpose(1, 0, 2),
        sh["wo"].reshape(n, f, d))).sum(0) / n


def _attend(qb, qpos, k, v, window):
    """qb [B, H, Dh] at positions ``qpos`` [B] against all keys k, v
    [T, KV, Dh] → [B, H, Dh]; one KV head's group of query heads at a
    time."""
    blk, h, dh = qb.shape
    kvh = k.shape[1]
    dist = qpos[:, None] - jnp.arange(k.shape[0])[None, :]
    ok = dist >= 0
    if window is not None:
        ok = ok & (dist < window)

    def group(args):
        qg, kg, vg = args                      # [B, G, Dh], [T, Dh] x 2
        s = jnp.einsum("qgd,kd->gqk", qg, kg) * (dh ** -0.5)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vg)

    out = jax.lax.map(group, (
        qb.reshape(blk, kvh, h // kvh, dh).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))   # [KV, B, G, Dh]
    return out.transpose(1, 0, 2, 3).reshape(blk, h, dh)


@partial(jax.jit, static_argnames=("w", "kind"))
def _layer(x, lp, w: Widths, kind: int):
    """One layer on one sequence: x [T, D] float32 (T a multiple of the
    query block), ``lp`` in whatever dtype the program holds it (cast to
    float32 where it is used) → (x, the layer's :func:`held_margin` [T])."""
    f32 = jnp.float32
    t = x.shape[0]
    a = lp["attn"]
    hin = _layer_norm(x, lp["ln1"]["scale"].astype(f32), w.eps)
    k = (hin @ a["wk"].astype(f32)).reshape(t, w.kv_heads, w.head_dim)
    v = (hin @ a["wv"].astype(f32)).reshape(t, w.kv_heads, w.head_dim)
    if kind:
        k = _rope_interleaved(k, jnp.arange(t), w.theta, w.rope_dim)
    blk = min(t, QUERY_BLOCK)

    def block(args):
        hb, start = args
        qpos = start + jnp.arange(blk)
        q = (hb @ a["wq"].astype(f32)).reshape(blk, w.heads, w.head_dim)
        if kind:
            q = _rope_interleaved(q, qpos, w.theta, w.rope_dim)
        o = _attend(q, qpos, k, v, w.window if kind else None)
        out = o.reshape(blk, w.heads * w.head_dim) @ a["wo"].astype(f32)
        out = out + experts_part(hb, lp["moe"], w) \
            + shared_part(hb, lp["shared"], w)
        return out, held_margin(hb, lp["moe"], w)

    out, margin = jax.lax.map(block, (
        hin.reshape(t // blk, blk, w.hidden), jnp.arange(0, t, blk)))
    return x + out.reshape(t, w.hidden), margin.reshape(t)


def _padded(tokens) -> np.ndarray:
    n = max(len(tokens), 1)
    size = dense._pow2_at_least(n, QUERY_BLOCK) if n <= PAD_TO \
        else -(-n // PAD_TO) * PAD_TO
    out = np.zeros(size, np.int32)
    out[:len(tokens)] = tokens
    return out


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the last layer's hidden states [T, D] float32, and
    each position's least :func:`held_margin` over the layers [T].
    Sequence-major: one sequence's stream is alive at a time."""
    emb = params["embed"]["tokens"]
    xs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device).astype(
                jnp.float32)
            margin = jnp.full(len(r), jnp.inf, jnp.float32)
            for kind, lp in zip(w.kinds, params["layers"]):
                x, m = _layer(x, lp, w, kind)
                margin = jnp.minimum(margin, m)
            xs.append(x)
            margins.append(margin)
    return xs, margins


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence."""
    return hidden_and_margins(w, params, token_rows, device)[0]


def _head(x, scale, embed, eps):
    """Final LayerNorm, then the TIED head: the embedding's rows."""
    return _layer_norm(x, scale, eps) @ embed.T


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' side of
    the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
    with jax.default_matmul_precision("highest"):
        out = _head(x[:len(tokens)],
                    dense._f32(params["final_norm"]["scale"], device),
                    dense._f32(params["embed"]["tokens"], device), w.eps)
    return np.asarray(out)


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
    that position (0.0: it IS the argmax)."""
    scale = dense._f32(params["final_norm"]["scale"], device)
    embed = dense._f32(params["embed"]["tokens"], device)
    gaps = []
    for p, o in zip(prompts, outputs):
        (x,), (margin,) = hidden_and_margins(
            w, params, [_padded(list(p) + list(o))], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(dense._pow2_at_least(len(o), 64), np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(_head(x[at], scale, embed, w.eps))[:len(o)]
        decided = np.asarray(margin)[at[:len(o)]] >= UNDECIDED_LOGIT_MARGIN
        gaps.append((logits.max(axis=-1) -
                     logits[np.arange(len(o)), np.asarray(o)])[decided])
    return np.concatenate(gaps)
