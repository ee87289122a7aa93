"""The plain reference for DeepSeek-V3's block (``model_type:
deepseek_v3``; GigaChat3.1-702B-A36B publishes it with a V head of 192), as
its ``config.json`` describes it, on ONE CHIP'S SHARE of an expert-parallel
deployment. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the EXPANDED form of latent
attention only — no cache, no absorbed product, no kernels, no batching, no
dispatch. One sequence at a time through every layer; a layer's matrices
are cast to float32 inside the jitted piece that uses them, one expert at a
time, so what this file adds to the device is one sequence's activations
and a matrix or two (the parameters themselves stay as the program holds
them).

For a token with normed hidden ``h``, layer ``l``:

- ``c_q = RMSNorm(h·W_qa)``; ``q = c_q·W_qb`` → ``heads`` x (``nope`` +
  ``rope``); ``[c_kv ; k_r] = h·W_kva``; ``c = RMSNorm(c_kv)``; rotate-half
  RoPE on each head's ``q_rope`` and on the one ``k_r`` all heads share
  (HF's module de-interleaves, then rotates halves: a weight loader's
  permutation, not another function). The inner norms use
  ``rms_norm_eps`` too.
- YaRN (``rope_scaling.rope_type: yarn``; HF ``_compute_yarn_parameters``):
  pair ``i`` of ``rope/2`` turns at ``f_i = theta ** (-2i / rope)``;
  ``low, high = floor, ceil`` of ``rope·ln(L0 / (2π·beta)) / (2·ln theta)``
  at ``beta_fast``, ``beta_slow``, clipped to ``[0, rope - 1]``; ``keep_i =
  1 - clip((i - low) / (high - low), 0, 1)``; the frequency is ``f_i /
  factor·(1 - keep_i) + f_i·keep_i``; sin / cos are scaled by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and the
  scores by ``(nope + rope) ** -0.5 · mscale(factor, mscale_all_dim) ** 2``,
  ``mscale(s, m) = 0.1·m·ln s + 1``.
- ``[k_nope_h ; v_h] = c·W_kvb`` a head (``nope`` + ``v``); ``s_ij =
  scale·(q_nope_h·k_nope_h + q_rope_h·k_rope)``; causal softmax; ``o_h = Σ
  p·v_h``; ``x ← x + concat_h(o_h)·W_o``.
- layers ``< first_k_dense_replace`` (and those off ``moe_layer_freq``):
  ``x ← x + (silu(h2·Wg) ⊙ (h2·Wi))·Wo`` at ``intermediate_size``. The
  others: ``z = sigmoid(h2·W_r)`` over ALL the router's experts; ``pick = z
  + b`` (``noaux_tc``: the bias picks and does not weigh); the experts in
  ``n_group`` equal groups, a group scored by the sum of its two highest
  picks, the picks outside the ``topk_group`` best groups set to 0.0 (as
  HF's gate masks them); ``S`` = the ``num_experts_per_tok`` largest of
  what stays; ``w_e = routed_scaling_factor · z_e / (Σ_{e'∈S} z_e' +
  1e-20)``; ``x ← x + Σ_{e ∈ S ∩ held} w_e·E_e(h2) + Shared(h2)``, SiLU-GLUs
  of ``moe_intermediate_size`` (the shared one ``n_shared_experts`` times
  as wide). **The share**: the file's ``n_routed_experts`` counts the
  experts HELD here, ``expert_share`` = ``{"router_experts",
  "first_expert"}`` gives the router's published width and the first expert
  held; what the absent experts would add is left out, here as in the
  program; the shared expert is on every chip and counts once.
- final RMSNorm; ``logits = x·W_head`` (untied).

Not built: the multi-token-prediction module (``num_nextn_predict_layers``
stays in the file as published; HF's ``deepseek_v3`` drops those weights on
load as well).

**What ``argmax_gaps`` judges**: as ``mimo_v2_decoder`` (same reason), the
tokens whose routing this file's own margins DECIDE
(``UNDECIDED_LOGIT_MARGIN``: this block's own reading of it, below). The
margin (:func:`held_margin`) covers the top-k boundary among the kept groups
and the GROUP CUT: where a held expert's group stands within a hair of the
kept / dropped boundary — or is kept while the boundary between the last
kept and the first dropped group is a hair wide, which changes whom the
held experts compete with — a bf16 program and this walk may part, each
soundly.

It reads the program's typed layer tree (``params["layers"]`` is a LIST;
``attn`` {wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo}, ``mlp`` or ``moe``
{router, router_bias?, wg, wi, wo over the held experts} and ``shared``
{wg, wi, wo}) and imports nothing from ``deepspeed_tpu``. It implements the
reference contract stated at the top of ``dense_decoder.py``; the head and
the norm are that file's."""

import math
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense

#: a token's routing is DECIDED when, in every sparse layer, no move of one
#: router logit smaller than this changes which held experts are selected or
#: whom they compete with (:func:`held_margin`). ``mimo_v2_decoder``'s 0.04
#: does not hold for this block: its router reads a stream of 7,168 values
#: (the program's router logits stray further from this walk's) and a held
#: expert's weight is scaled by 2.5, so one flipped expert moves a logit by
#: up to 1.08. Between two readings on the v5e (PERF.md §6, PR 35; 110,592
#: generated tokens of the sound bf16 program at contexts to 1,600, two
#: seeds): where the sound program's choice stands more than 0.1 under this
#: file's argmax (a flipped held expert: 983 tokens), the margin reads at most
#: 0.076 (at 0.04 the cell's own run failed: 12 judged tokens over 0.25 in
#: 48,171; from 0.08 up the largest gap is 0.099, no flip left in 24,639); a
#: program with ``W_kvb`` rounded to float8 (e4m3) is caught at every margin
#: up to 0.2 (at 0.16: 4 of 904 judged tokens over the runner's 0.25, 32 of 904
#: with the latent pool in float8) and no longer at 0.3 (0 of 181). At 0.16,
#: 92.5% of tokens are left out: some 1,800 of a run's 24,000 are judged.
UNDECIDED_LOGIT_MARGIN = 0.16

#: queries a block of the attention scores against all keys (the scores of
#: 64 heads over a 5,120-token sequence are 335 MB a block at 256)
QUERY_BLOCK = 256
#: sequences are right-padded to a multiple of this (few shapes to compile;
#: a causal mask makes the tail harmless)
PAD_TO = 1024


@dataclass(frozen=True)
class Widths:
    hidden: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    eps: float
    theta: float
    #: (factor, original length, beta_fast, beta_slow, mscale,
    #: mscale_all_dim) or None
    yarn: Optional[Tuple[float, int, float, float, float, float]]
    layers: int
    sparse: Tuple[int, ...]         # 0 dense, 1 experts
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int
    router_experts: int
    first_expert: int
    held_experts: int
    per_token: int
    groups: int
    groups_kept: int
    routed_scale: float
    norm_topk: bool
    vocab: int

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        layers = int(hf["num_hidden_layers"])
        held = int(hf["n_routed_experts"])
        share = hf.get("expert_share") or {"router_experts": held,
                                           "first_expert": 0}
        dense_k = int(hf.get("first_k_dense_replace", 0))
        freq = int(hf.get("moe_layer_freq", 1))
        sc = hf.get("rope_scaling") or {}
        yarn = None
        if sc.get("rope_type", sc.get("type", "default")) == "yarn":
            yarn = (float(sc["factor"]),
                    int(sc["original_max_position_embeddings"]),
                    float(sc.get("beta_fast") or 32),
                    float(sc.get("beta_slow") or 1),
                    float(sc.get("mscale") or 0),
                    float(sc.get("mscale_all_dim") or 0))
        return cls(
            hidden=int(hf["hidden_size"]),
            heads=int(hf["num_attention_heads"]),
            q_lora=int(hf["q_lora_rank"]), kv_lora=int(hf["kv_lora_rank"]),
            nope=int(hf["qk_nope_head_dim"]), rope=int(hf["qk_rope_head_dim"]),
            v_head=int(hf["v_head_dim"]), eps=float(hf["rms_norm_eps"]),
            theta=float(hf["rope_theta"]), yarn=yarn, layers=layers,
            sparse=tuple(int(l >= dense_k and l % freq == 0)
                         for l in range(layers)),
            dense_ffn=int(hf["intermediate_size"]),
            expert_ffn=int(hf["moe_intermediate_size"]),
            shared_ffn=int(hf.get("n_shared_experts") or 0) *
            int(hf["moe_intermediate_size"]),
            router_experts=int(share["router_experts"]),
            first_expert=int(share["first_expert"]), held_experts=held,
            per_token=int(hf["num_experts_per_tok"]),
            groups=int(hf.get("n_group") or 1),
            groups_kept=int(hf.get("topk_group") or 1),
            routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
            norm_topk=bool(hf["norm_topk_prob"]),
            vocab=int(hf["vocab_size"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies ON THIS CHIP, forward: each layer's five
    latent-attention matrices (expanded form); the dense layer's GLU; in a
    sparse layer the router at its full width, the shared expert and, of
    the token's ``per_token`` experts, the share held here (``per_token x
    held / router_experts`` of them on average, three matrices each); the
    untied head over the vocabulary slice."""
    attn = w.hidden * w.q_lora + w.q_lora * w.heads * (w.nope + w.rope) \
        + w.hidden * (w.kv_lora + w.rope) \
        + w.kv_lora * w.heads * (w.nope + w.v_head) \
        + w.heads * w.v_head * w.hidden
    total = w.hidden * w.vocab
    for sparse in w.sparse:
        total += attn
        if sparse:
            total += w.hidden * w.router_experts \
                + 3 * w.hidden * w.shared_ffn + round(
                    w.per_token * w.held_experts / w.router_experts
                    * 3 * w.hidden * w.expert_ffn)
        else:
            total += 3 * w.hidden * w.dense_ffn
    return int(total)


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(w: Widths) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies: plain, or YaRN's."""
    i = np.arange(w.rope // 2, dtype=np.float64)
    f = w.theta ** (-2.0 * i / w.rope)
    if w.yarn is None:
        return f
    factor, length, fast, slow = w.yarn[:4]

    def correction_dim(beta: float) -> float:
        return w.rope * math.log(length / (beta * 2 * math.pi)) \
            / (2 * math.log(w.theta))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), w.rope - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return f / factor * (1.0 - keep) + f * keep


def score_scale(w: Widths) -> float:
    scale = (w.nope + w.rope) ** -0.5
    if w.yarn is not None and w.yarn[5]:
        scale *= _mscale(w.yarn[0], w.yarn[5]) ** 2
    return scale


def _rope(x, positions, w: Widths):
    """x [T, H, rope]: rotate-half at the configured frequencies."""
    ang = positions.astype(jnp.float32)[:, None] * \
        jnp.asarray(rope_frequencies(w), jnp.float32)[None]
    mag = 1.0
    if w.yarn is not None:
        factor, _, _, _, m, m_all = w.yarn
        mag = _mscale(factor, m) / _mscale(factor, m_all) if m and m_all \
            else _mscale(factor, 1.0)
    cos = mag * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = mag * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, scale: float):
    """q, k [T, H, Dk], v [T, H, Dv] → [T, H, Dv], causal; T a multiple of
    the query block."""
    t, h, dk = q.shape
    blk = min(t, QUERY_BLOCK)
    kpos = jnp.arange(t)

    def block(args):
        qb, start = args
        qpos = start + jnp.arange(blk)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(t // blk, blk, h, dk),
                              jnp.arange(0, t, blk)))
    return out.reshape(t, h, v.shape[-1])


def _up(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("w",))
def attention_block(x, ln1, a, w: Widths):
    """x [T, D] float32 → x + attention(RMSNorm(x)), expanded form."""
    t = x.shape[0]
    pos = jnp.arange(t)
    a = _up(a)
    hin = dense._rms_norm(x, ln1["scale"].astype(jnp.float32), w.eps)
    c_q = dense._rms_norm(hin @ a["wq_a"], a["q_norm"]["scale"], w.eps)
    q = (c_q @ a["wq_b"]).reshape(t, w.heads, w.nope + w.rope)
    kv_a = hin @ a["wkv_a"]
    c = dense._rms_norm(kv_a[:, :w.kv_lora], a["kv_norm"]["scale"], w.eps)
    k_rope = _rope(kv_a[:, None, w.kv_lora:], pos, w)          # [T, 1, rope]
    kv = (c @ a["wkv_b"]).reshape(t, w.heads, w.nope + w.v_head)
    q = jnp.concatenate([q[..., :w.nope], _rope(q[..., w.nope:], pos, w)],
                        -1)
    k = jnp.concatenate([kv[..., :w.nope],
                         jnp.broadcast_to(k_rope, (t, w.heads, w.rope))], -1)
    o = _attention(q, k, kv[..., w.nope:], score_scale(w))
    return x + o.reshape(t, w.heads * w.v_head) @ a["wo"]


def _glu(hin, wg, wi, wo):
    """One SiLU-GLU; its matrices cast to float32 here, one at a time."""
    f32 = jnp.float32
    return (jax.nn.silu(hin @ wg.astype(f32)) * (hin @ wi.astype(f32))) \
        @ wo.astype(f32)


def _scores(hin, m):
    """hin [T, D] → (router logits, scores z = sigmoid(logits), picks z + b
    that the selection compares), each [T, router_experts] float32."""
    logits = hin @ m["router"].astype(jnp.float32)
    z = jax.nn.sigmoid(logits)
    if "router_bias" in m:
        return logits, z, z + m["router_bias"].astype(jnp.float32)
    return logits, z, z


def _group_cut(pick, w: Widths):
    """pick [T, E] → (the picks with those outside the kept groups set to
    0.0, each group's score [T, groups], which groups are kept [T, groups]
    bool). One group: nothing is cut."""
    t = pick.shape[0]
    if w.groups == 1:
        return pick, None, jnp.ones((t, 1), bool)
    grouped = pick.reshape(t, w.groups, -1)
    score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, kept = jax.lax.top_k(score, w.groups_kept)
    in_kept = jax.nn.one_hot(kept, w.groups, dtype=bool).any(axis=1)
    return jnp.where(in_kept[..., None], grouped, 0.0).reshape(pick.shape), \
        score, in_kept


def route(hin, m, w: Widths):
    """hin [T, D] → the weight of every one of the router's experts for
    every token, [T, router_experts] float32 (0 where not selected), and
    the selected ids [T, per_token]."""
    _, z, pick = _scores(hin, m)
    _, sel = jax.lax.top_k(_group_cut(pick, w)[0], w.per_token)
    kept = jnp.take_along_axis(z, sel, axis=-1)
    if w.norm_topk:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    kept = kept * w.routed_scale
    chosen = jax.nn.one_hot(sel, w.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", kept, chosen), sel


def held_margin(hin, m, w: Widths):
    """hin [T, D] → [T] float32: the least move of ONE router logit that
    changes which HELD experts are selected, or whom they compete with.

    Among the kept groups, as ``mimo_v2_decoder.held_margin``: a selected
    held expert leaves when its pick falls to the best unselected pick, an
    unselected one (of a kept group) enters when its pick rises to the last
    selected: ``|l_e − logit(that pick − b_e)|``. The group cut: a held
    expert's group that is dropped enters when its score (the sum of its
    two highest picks) rises to the last kept group's; where it is kept,
    the cut moves when the last kept and the first dropped group's scores
    meet (the held group leaves, or a held expert's rivals change). A
    score gap is turned into a logit move by the steepest ``z(1 − z)``
    among the groups' two highest picks."""
    logits, z, pick = _scores(hin, m)
    cut, score, in_kept = _group_cut(pick, w)
    top = jax.lax.top_k(cut, w.per_token + 1)[0]
    last_in = top[:, w.per_token - 1:w.per_token]
    best_out = top[:, w.per_token:]
    held = slice(w.first_expert, w.first_expert + w.held_experts)
    bias = m["router_bias"][held].astype(jnp.float32) \
        if "router_bias" in m else 0.0
    size = w.router_experts // w.groups
    group_of = np.arange(w.first_expert,
                         w.first_expert + w.held_experts) // size
    open_ = in_kept[:, group_of]                  # [T, held]: group kept
    selected = cut[:, held] >= last_in
    target = jnp.where(selected, best_out, last_in) - bias     # a score
    reachable = (target > 0.0) & (target < 1.0) & open_
    safe = jnp.where(reachable, target, 0.5)
    move = jnp.abs(logits[:, held] - (jnp.log(safe) - jnp.log1p(-safe)))
    margin = jnp.min(jnp.where(reachable, move, jnp.inf), axis=-1)
    if w.groups == 1 or w.groups_kept >= w.groups:
        return margin
    ranked = jax.lax.top_k(score, w.groups_kept + 1)[0]
    last_kept, first_dropped = ranked[:, -2], ranked[:, -1]
    t = pick.shape[0]
    zg = z.reshape(t, w.groups, -1)
    _, two = jax.lax.top_k(pick.reshape(t, w.groups, -1), 2)
    z2 = jnp.take_along_axis(zg, two, axis=-1)
    steepest = jnp.max(z2 * (1.0 - z2), axis=(1, 2))           # [T]
    mine = np.unique(group_of)
    gap = jnp.min(jnp.where(in_kept[:, mine],
                            (last_kept - first_dropped)[:, None],
                            last_kept[:, None] - score[:, mine]), axis=-1)
    return jnp.minimum(margin, gap / jnp.maximum(steepest, 1e-6))


def experts_part(hin, m, w: Widths):
    """The part of the sparse layer's output that the HELD routed experts
    give: hin [T, D] (the normed input) → [T, D]. With every expert held it
    is the routed part of the whole layer. Every held expert computes every
    token, one expert at a time, weighed (0 where the token did not pick
    it)."""
    weight, _ = route(hin, m, w)
    mine = weight[:, w.first_expert:w.first_expert + w.held_experts]

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * _glu(hin, wg, wi, wo)

    return jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], mine.T)).sum(0)


@partial(jax.jit, static_argnames=("w",))
def sparse_block(x, ln2, m, shared, w: Widths):
    """x [T, D] → (x + the held experts' part + the shared expert, the
    layer's :func:`held_margin` [T])."""
    hin = dense._rms_norm(x, ln2["scale"].astype(jnp.float32), w.eps)
    out = x + experts_part(hin, m, w)
    if shared is not None:
        out = out + _glu(hin, shared["wg"], shared["wi"], shared["wo"])
    return out, held_margin(hin, m, w)


@partial(jax.jit, static_argnames=("w",))
def dense_block(x, ln2, m, w: Widths):
    hin = dense._rms_norm(x, ln2["scale"].astype(jnp.float32), w.eps)
    return x + _glu(hin, m["wg"], m["wi"], m["wo"])


def _padded(tokens: Sequence[int]) -> np.ndarray:
    out = np.zeros(-(-len(tokens) // PAD_TO) * PAD_TO, np.int32)
    out[:len(tokens)] = tokens
    return out


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the last layer's hidden states [T, D] float32, and
    each position's least :func:`held_margin` over the sparse layers [T].
    Sequence-major: one sequence's activations are alive at a time, and the
    parameters are read where they lie."""
    emb = params["embed"]["tokens"]
    xs, margins = [], []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device
                               ).astype(jnp.float32)
            margin = jnp.full(len(r), jnp.inf, jnp.float32)
            for lp, sparse in zip(params["layers"], w.sparse):
                x = attention_block(x, lp["ln1"], lp["attn"], w)
                if sparse:
                    x, m = sparse_block(x, lp["ln2"], lp["moe"],
                                        lp.get("shared"), w)
                    margin = jnp.minimum(margin, m)
                else:
                    x = dense_block(x, lp["ln2"], lp["mlp"], w)
            xs.append(x)
            margins.append(margin)
    return xs, margins


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence."""
    return hidden_and_margins(w, params, token_rows, device)[0]


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' side of
    the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [_padded(list(tokens))], device)
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
    that position (0.0: it IS the argmax)."""
    rows = [_padded(list(p) + list(o)) for p, o in zip(prompts, outputs)]
    scale = dense._f32(params["final_norm"]["scale"], device)
    gaps = []
    for p, o, row in zip(prompts, outputs, rows):
        (x,), (margin,) = hidden_and_margins(w, params, [row], device)
        # logits at position len(p)-1+j predict generated token j
        at = np.zeros(-(-len(o) // PAD_TO) * PAD_TO, np.int32)
        at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(_head(x[at], scale, params["lm_head"],
                                      w.eps))[:len(o)]
        decided = np.asarray(margin)[at[:len(o)]] >= UNDECIDED_LOGIT_MARGIN
        gaps.append((logits.max(axis=-1) -
                     logits[np.arange(len(o)), np.asarray(o)])[decided])
    return np.concatenate(gaps)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, lm_head, eps):
    """The final norm and the untied head, the head cast here (a float32
    copy of a 7168 x 16032 head is 460 MB: it lives for the call)."""
    return dense._rms_norm(x, scale, eps) @ lm_head.astype(jnp.float32)
