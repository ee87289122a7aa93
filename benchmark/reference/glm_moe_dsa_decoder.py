"""The plain reference for GLM-5.2's block (``model_type: glm_moe_dsa``), as
its ``config.json`` describes it, on ONE CHIP'S SHARE of an expert-parallel
deployment: DeepSeek-V3's latent block at other widths with DeepSeek-V3.2's
sparse-attention INDEXER in front of the softmax. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
the expanded form of latent attention, the picks applied as a MASK on the
dense scores — no cache, no pool of index keys, no gather, no kernels, no
batching. The latent projections, the rotary table, the router, the experts'
share, the shared expert and the head are ``deepseek_v3_decoder``'s, which
that file parametrises by widths; what is written here is the indexer, the
mask, and the walk in BLOCKS that lets a 21,000-token sequence fit beside
the program's parameters and arena (heads eight at a time, per-token parts
1,024 tokens at a time, the indexer 64 queries at a time).

For a token with normed hidden ``h``, in a layer whose ``indexer_types``
entry is ``full`` (tree ``indexer`` {wq, wk, k_norm {scale, bias}, ww}),
with ``c_q = RMSNorm(h·W_qa)`` (the latent the heads' queries use), ``J =
index_n_heads``, ``d = index_head_dim``:

- ``q^I_{t,j} = RoPE(c_q,t · W^I_q)[j]``: the FIRST ``qk_rope_head_dim``
  dims of each head rotated (rotate-half, the layer's own table), the rest
  passed through; ``k^I_s = RoPE(LayerNorm(h_s · W^I_k))`` (scale and bias,
  eps 1e-6): one key of ``d`` a token; ``w_t = (h_t · W^I_w) · J^-0.5``;
- ``I_{t,s} = d^-0.5 · Σ_j w_{t,j} · ReLU(q^I_{t,j} · k^I_s)`` for ``s ≤
  t``; ``S_t`` = the ``min(index_topk, t + 1)`` positions of the highest
  ``I_{t,·}`` (``lax.top_k``: ties to the lower position);
- the latent softmax of the layer runs over ``s ∈ S_t`` alone.

A layer whose entry is ``shared`` has no indexer weights and takes ``S_t``
from the nearest ``full`` layer below it. Not built (as the program):
DeepSeek-V3.2's Hadamard rotation of the index vectors (orthogonal: it
changes no score) and their FP8 storage (a storage format); the
multi-token-prediction module.

**What ``argmax_gaps`` judges**: as ``deepseek_v3_decoder``, the tokens
whose ROUTING this file's margin decides (``UNDECIDED_LOGIT_MARGIN``;
:func:`deepseek_v3_decoder.held_margin` with one group) — and, of the
positions whose query PICKS (its context is past ``index_topk``), those
whose ARGMAX this walk decides by ``UNDECIDED_ARGMAX_MARGIN``. A margin on
the picks' SCORES judges nothing: at 20,000 keys the k-th and the (k+1)-th
score are always a hair apart. And a swap there is NOT one key of 2,048 in
a softmax that hardly notices: with random weights an indexer's scores say
nothing of the attention's, so the keys at the boundary carry an average
share of the softmax, the layer's output is the mean of 2,048 unrelated
value vectors — of the size of ONE over their square root — and ``m``
swapped picks move it by ``sqrt(2m / 2048)`` of itself: 17% at the thirty
swaps that rounding the index vectors to bf16 causes among 20,000 keys (a
trained indexer ranks by the attention's own mass, and its boundary keys
carry none). On the v5e the SOUND bf16 program's logits stand 0.3–0.6 from
this walk's at EVERY position past ``index_topk`` and within bf16's own
0.1 once it is handed this walk's picks (tools/chip_check_glm_dsa.py,
``reference_picks``; PERF.md §6, PR 52): two sound walks part there, as
two sound routers do at a routing near-tie, and the same rule holds — a
token is judged where no such parting can change the verdict.

It reads the program's typed layer tree and imports nothing from
``deepspeed_tpu``. It implements the reference contract stated at the top
of ``dense_decoder.py``."""

from dataclasses import dataclass
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import deepseek_v3_decoder as latent
from benchmark.reference import dense_decoder as dense

#: a token's routing is DECIDED when no move of one router logit smaller
#: than this changes which held experts are selected (``latent.held_margin``
#: with ``n_group`` 1: the top-k boundary alone). ``deepseek_v3_decoder``'s
#: value, read on the v5e for a 7,168-wide stream and a weight scale of 2.5
#: (PERF.md §6, PR 35); this block's stream is 6,144 wide with the same
#: scale, and tools/chip_check_glm_dsa.py reads the sound program and the
#: float8 control under it (PERF.md §6, PR 52)
UNDECIDED_LOGIT_MARGIN = 0.16
#: a position whose query picks is judged where this walk's highest logit
#: stands at least this far over its second: a sound program's picks part
#: from this walk's at the boundary and move a logit by some tenths (the
#: module docstring), which cannot change an argmax decided by more. Between
#: two readings on the v5e (PERF.md §6, PR 52)
UNDECIDED_ARGMAX_MARGIN = 0.8

#: heads whose expanded q, k, v and scores are alive at once (of 64: q, k
#: and v of 21,504 tokens are 1.4 GB each for all heads, 176 MB for eight)
HEAD_BLOCK = 8
#: queries an indexer scores at once against all keys (32 heads x 21,504
#: keys x 4 B = 2.75 MB a query)
INDEX_BLOCK = 64
#: eps of the index key's LayerNorm (DeepSeek-V3.2's reference code)
INDEX_NORM_EPS = 1e-6


@dataclass(frozen=True)
class Widths(latent.Widths):
    index_heads: int
    index_dim: int
    index_topk: int
    owners: Tuple[int, ...]         # 1: the layer owns an indexer

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        rope = hf.get("rope_parameters") or {}
        base = latent.Widths.from_hf(
            {**hf, "rope_theta": rope.get("rope_theta",
                                          hf.get("rope_theta", 1e4)),
             "rope_scaling": None})
        layers = base.layers
        types = hf.get("indexer_types")
        if types is None:
            freq = int(hf.get("index_topk_freq") or 1)
            skip = int(hf.get("index_skip_topk_offset") or 0)
            types = ["full" if l < skip or (l - skip) % freq == freq - 1
                     else "shared" for l in range(layers)]
        return cls(**base.__dict__, index_heads=int(hf["index_n_heads"]),
                   index_dim=int(hf["index_head_dim"]),
                   index_topk=int(hf["index_topk"]),
                   owners=tuple(int(t == "full") for t in types))


def matmul_params_per_token(w: Widths) -> int:
    """``deepseek_v3_decoder``'s count, and in each layer that owns an
    indexer its three projections."""
    indexer = w.q_lora * w.index_heads * w.index_dim \
        + w.hidden * w.index_dim + w.hidden * w.index_heads
    return latent.matmul_params_per_token(w) + sum(w.owners) * indexer


def _rope_leading(x, positions, w: Widths):
    """x [T, H, d]: rotate the leading ``rope`` dims, pass the rest."""
    return jnp.concatenate([latent._rope(x[..., :w.rope], positions, w),
                            x[..., w.rope:]], axis=-1)


@partial(jax.jit, static_argnames=("w",))
def index_vectors(hin, c_q, ix, w: Widths):
    """hin [T, D] (the layer's normed input), c_q [T, q_lora] → (q^I [T, J,
    d], k^I [T, d], w_t [T, J] with ``J^-0.5 · d^-0.5`` folded in)."""
    t = hin.shape[0]
    pos = jnp.arange(t)
    ix = latent._up(ix)
    q = (c_q @ ix["wq"]).reshape(t, w.index_heads, w.index_dim)
    k = hin @ ix["wk"]
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
        * ix["k_norm"]["scale"] + ix["k_norm"]["bias"]
    weight = (hin @ ix["ww"]) * (w.index_heads * w.index_dim) ** -0.5
    return _rope_leading(q, pos, w), \
        _rope_leading(k[:, None], pos, w)[:, 0], weight


def index_scores(q, k, weight):
    """``I[t, s]`` for every pair, [T', T] float32 (no causal mask yet)."""
    s = jnp.einsum("tjd,sd->tjs", q, k)
    return jnp.einsum("tjs,tj->ts", jnp.maximum(s, 0.0), weight)


@partial(jax.jit, static_argnames=("w",))
def picks_mask(q, k, weight, w: Widths):
    """The picks of every query as a mask [T, T] bool: ``mask[t, s]`` where
    ``s ∈ S_t``. T a multiple of the index block."""
    t = q.shape[0]
    blk = min(t, INDEX_BLOCK)
    kpos = jnp.arange(t)
    kk = min(w.index_topk, t)

    def block(args):
        qb, wb, start = args
        visible = (start + jnp.arange(blk))[:, None] >= kpos[None]
        scores = jnp.where(visible, index_scores(qb, k, wb), -jnp.inf)
        idx = jax.lax.top_k(scores, kk)[1]
        return jnp.zeros((blk, t), bool).at[
            jnp.arange(blk)[:, None], idx].set(True) & visible

    return jax.lax.map(block, (
        q.reshape(t // blk, blk, *q.shape[1:]),
        weight.reshape(t // blk, blk, -1),
        jnp.arange(0, t, blk))).reshape(t, t)


def _attention(q, k, v, mask, scale: float):
    """q, k [T, H, Dk], v [T, H, Dv], mask [T, T] bool (causal already) →
    [T, H, Dv]; T a multiple of the query block."""
    t, h, dk = q.shape
    blk = min(t, latent.QUERY_BLOCK)

    def block(args):
        qb, mb = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where(mb[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(t // blk, blk, h, dk),
                              mask.reshape(t // blk, blk, t)))
    return out.reshape(t, h, v.shape[-1])


@partial(jax.jit, static_argnames=("w",))
def attention_inputs(x, ln1, a, w: Widths):
    """x [T, D] → (the normed input, c_q, the normed latent c, the rotated
    shared key [T, 1, rope])."""
    pos = jnp.arange(x.shape[0])
    hin = dense._rms_norm(x, ln1["scale"].astype(jnp.float32), w.eps)
    c_q = dense._rms_norm(hin @ a["wq_a"].astype(jnp.float32),
                          a["q_norm"]["scale"].astype(jnp.float32), w.eps)
    kv_a = hin @ a["wkv_a"].astype(jnp.float32)
    c = dense._rms_norm(kv_a[:, :w.kv_lora],
                        a["kv_norm"]["scale"].astype(jnp.float32), w.eps)
    return hin, c_q, c, latent._rope(kv_a[:, None, w.kv_lora:], pos, w)


@partial(jax.jit, static_argnames=("w",))
def attention_heads(c_q, c, k_rope, mask, wq_b, wkv_b, wo, w: Widths):
    """The contribution of a BLOCK of heads to the layer's output: their
    columns of ``W_qb`` and ``W_kvb``, their rows of ``W_o`` → [T, D]."""
    t = c_q.shape[0]
    pos = jnp.arange(t)
    f32 = jnp.float32
    q = (c_q @ wq_b.astype(f32)).reshape(t, -1, w.nope + w.rope)
    heads = q.shape[1]
    kv = (c @ wkv_b.astype(f32)).reshape(t, heads, w.nope + w.v_head)
    q = jnp.concatenate([q[..., :w.nope],
                         latent._rope(q[..., w.nope:], pos, w)], -1)
    k = jnp.concatenate([kv[..., :w.nope],
                         jnp.broadcast_to(k_rope, (t, heads, w.rope))], -1)
    o = _attention(q, k, kv[..., w.nope:], mask, latent.score_scale(w))
    return o.reshape(t, heads * w.v_head) @ wo.astype(f32)


def attention_block(x, lp, mask, w: Widths):
    """x [T, D] float32 → (x + attention over the picks, the picks this
    layer leaves the next: its own where it owns an indexer, else
    ``mask`` as it came)."""
    a = lp["attn"]
    hin, c_q, c, k_rope = attention_inputs(x, lp["ln1"], a, w)
    if "indexer" in lp:
        mask = picks_mask(*index_vectors(hin, c_q, lp["indexer"], w), w)
    hb = min(HEAD_BLOCK, w.heads)
    qw, kw, vw = w.nope + w.rope, w.nope + w.v_head, w.v_head
    for h0 in range(0, w.heads, hb):
        x = x + attention_heads(
            c_q, c, k_rope, mask, a["wq_b"][:, h0 * qw:(h0 + hb) * qw],
            a["wkv_b"][:, h0 * kw:(h0 + hb) * kw],
            a["wo"][h0 * vw:(h0 + hb) * vw], w)
    return x, mask


def _by_tokens(fn, x, *args):
    """``fn(x_block, *args)`` over blocks of ``PAD_TO`` tokens (what acts
    on a token alone; T is a multiple), the results joined again."""
    blk = latent.PAD_TO
    if x.shape[0] <= blk:
        return fn(x, *args)
    outs = [fn(x[i:i + blk], *args) for i in range(0, x.shape[0], blk)]
    if isinstance(outs[0], tuple):
        return tuple(jnp.concatenate(part) for part in zip(*outs))
    return jnp.concatenate(outs)


def hidden_and_margins(w: Widths, params, token_rows: List[np.ndarray],
                       device):
    """Per sequence: the last layer's hidden states [T, D] float32, each
    position's least ``held_margin`` over the sparse layers [T], and the
    picks [T, T] bool of each layer that owns an indexer, in layer order
    (0.46 GB each at 21,504 positions: drop what is not wanted). One
    sequence's activations are alive at a time."""
    emb = params["embed"]["tokens"]
    xs, margins, masks = [], [], []
    with jax.default_matmul_precision("highest"):
        for r in token_rows:
            x = jax.device_put(emb[jnp.asarray(r)], device
                               ).astype(jnp.float32)
            margin = jnp.full(len(r), jnp.inf, jnp.float32)
            mask, owned = None, []
            for lp, sparse in zip(params["layers"], w.sparse):
                x, mask = attention_block(x, lp, mask, w)
                if "indexer" in lp:
                    owned.append(mask)
                if sparse:
                    x, m = _by_tokens(
                        lambda xb, lp=lp: latent.sparse_block(
                            xb, lp["ln2"], lp["moe"], lp.get("shared"), w),
                        x)
                    margin = jnp.minimum(margin, m)
                else:
                    x = _by_tokens(
                        lambda xb, lp=lp: latent.dense_block(
                            xb, lp["ln2"], lp["mlp"], w), x)
            xs.append(x)
            margins.append(margin)
            masks.append(owned)
    return xs, margins, masks


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device):
    """Last-layer hidden states, one [T, D] float32 array per sequence."""
    return hidden_and_margins(w, params, token_rows, device)[0]


def _logits_at(w: Widths, params, x, at: np.ndarray, device) -> np.ndarray:
    """The head's logits at positions ``at`` of one sequence's hidden
    states, ``PAD_TO`` positions a call."""
    scale = dense._f32(params["final_norm"]["scale"], device)
    pad = -(-len(at) // latent.PAD_TO) * latent.PAD_TO
    idx = np.zeros(pad, np.int32)
    idx[:len(at)] = at
    with jax.default_matmul_precision("highest"):
        out = [np.asarray(latent._head(
            x[idx[i:i + latent.PAD_TO]], scale, params["lm_head"], w.eps))
            for i in range(0, pad, latent.PAD_TO)]
    return np.concatenate(out)[:len(at)]


def logits_of(w: Widths, params, tokens, device) -> np.ndarray:
    """Full-forward logits [T, vocab] of one sequence (the tests' side of
    the comparison; T is padded and cut back)."""
    (x,) = final_hidden(w, params, [latent._padded(list(tokens))], device)
    return _logits_at(w, params, x, np.arange(len(tokens)), device)


def picks_of(w: Widths, params, tokens, device) -> List[np.ndarray]:
    """The picks ``[T, T]`` bool of each layer that owns an indexer, in
    layer order, and the k-th / (k+1)-th score gap of each query there
    ``[T]`` (``inf`` where a query sees ``index_topk`` keys or fewer): what
    the tests hold the program's picks against, set for set."""
    row = latent._padded(list(tokens))
    t = len(tokens)
    out = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(row)].astype(jnp.float32)
        mask = None
        for lp, sparse in zip(params["layers"], w.sparse):
            if "indexer" in lp:
                hin, c_q, _, _ = attention_inputs(x, lp["ln1"], lp["attn"],
                                                  w)
                q, k, weight = index_vectors(hin, c_q, lp["indexer"], w)
                scores = jnp.where(jnp.tril(jnp.ones((len(row),) * 2,
                                                     bool)),
                                   index_scores(q, k, weight), -jnp.inf)
                top = jax.lax.top_k(scores, min(w.index_topk + 1,
                                                len(row)))[0]
                gap = top[:, -2] - top[:, -1] \
                    if top.shape[1] > w.index_topk else \
                    jnp.full(len(row), jnp.inf)
            x, mask = attention_block(x, lp, mask, w)
            if "indexer" in lp:
                out.append((np.asarray(mask)[:t, :t], np.asarray(gap)[:t]))
            block = latent.sparse_block if sparse else latent.dense_block
            args = (lp["ln2"], lp["moe"], lp.get("shared"), w) if sparse \
                else (lp["ln2"], lp["mlp"], w)
            x = block(x, *args)
            x = x[0] if sparse else x
    return out


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last). No balance term (``noaux_tc``)."""
    rows = [np.asarray(r, np.int32) for r in batch]
    total = 0.0
    for r in rows:
        logits = jnp.asarray(logits_of(w, params, r, device))[:-1]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, jnp.asarray(r[1:])[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(nll))
    return total / sum(len(r) - 1 for r in rows)


def teacher_forced(w: Widths, params, prompts: Sequence, outputs: Sequence,
                   device) -> dict:
    """Every generated token of every request, flattened, as this walk sees
    it: ``gap`` (how far it stands below this walk's argmax at its
    position; 0.0: it IS the argmax), ``routing`` (the position's least
    ``held_margin``), ``lead`` (this walk's highest logit less its second)
    and ``picks`` (the position's query sees more than ``index_topk``
    keys). What :func:`argmax_gaps` filters, and what a tool reads the
    margins from (tools/chip_control_glm_dsa.py)."""
    out = {"gap": [], "routing": [], "lead": [], "picks": []}
    for p, o in zip(prompts, outputs):
        row = latent._padded(list(p) + list(o))
        (x,), (margin,), picks = hidden_and_margins(w, params, [row], device)
        del picks
        # logits at position len(p)-1+j predict generated token j
        at = np.arange(len(p) - 1, len(p) - 1 + len(o))
        logits = _logits_at(w, params, x, at, device)
        top = np.partition(logits, -2, axis=-1)[:, -2:]
        out["gap"].append(top[:, 1] - logits[np.arange(len(o)),
                                             np.asarray(o)])
        out["routing"].append(np.asarray(margin)[at])
        out["lead"].append(top[:, 1] - top[:, 0])
        out["picks"].append(at + 1 > w.index_topk)
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def argmax_gaps(w: Widths, params, prompts: Sequence, outputs: Sequence,
                device) -> np.ndarray:
    """Teacher-forced check of generated tokens: for every generated token
    whose routing is decided and — where its position's query picks —
    whose argmax is (the module docstring; flattened over the requests),
    how far the reference scores it below its own argmax at that position
    (0.0: it IS the argmax)."""
    seen = teacher_forced(w, params, prompts, outputs, device)
    decided = (seen["routing"] >= UNDECIDED_LOGIT_MARGIN) & (
        ~seen["picks"].astype(bool) |
        (seen["lead"] >= UNDECIDED_ARGMAX_MARGIN))
    return seen["gap"][decided]
