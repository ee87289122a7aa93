"""Typed layer stacks: a decoder whose layers are NOT one scanned block.

``DecoderConfig.layer_kinds`` names each layer's attention kind (0 = full
causal, 1 = window, 2 = latent) and ``layer_sparse`` whether its feed-forward is
sparse experts or a dense MLP (leading dense layers). The kinds differ in
SHAPE — KV heads, rotary base, a learned sink on the window kind, the dense
width — so the layers cannot share one stacked tree: ``params["layers"]``
is a list of per-layer trees and the layer loop is unrolled. The first
family built this way is MiMo-V2 (``hf_loader``: ``mimo_v2``); the
equations, for layer ``l`` of kind ``a``:

- ``h = norm(x)``; ``q = h·Wq → [T, H, Dk]``, ``k = h·Wk → [T, KV_a, Dk]``,
  ``v = value_scale · (h·Wv) → [T, KV_a, Dv]``; rotate-half RoPE with base
  ``θ_a`` on the first ``rope_dim`` dims of every q and k head;
- scores ``q_i·k_j / √Dk`` for ``0 ≤ i − j`` and, on window layers,
  ``i − j < sliding_window``; a window layer's learned ``sink[h]`` joins
  the softmax as one more column that takes mass and gives no value:
  ``p_ij = exp(s_ij) / (exp(sink_h) + Σ_j' exp(s_ij'))``;
- ``x ← x + o·Wo``; ``x ← x + ffn(norm(x))``, a SiLU-GLU of
  ``dense_intermediate_size`` or the experts (``parallel/moe.py``).

A LATENT layer (kind 2; DeepSeek-V3's MLA, ``hf_loader``: ``deepseek_v3``)
projects through two bottlenecks and caches the second:

- ``c_q = RMSNorm(h·W_qa)``; ``q = c_q·W_qb → [T, H, nope + rope]``;
  ``[c_kv ; k_r] = h·W_kva``; ``c = RMSNorm(c_kv)``; rotate-half RoPE
  (``rope_table``: YaRN frequencies where configured) on each head's
  ``q_rope`` and on the ONE ``k_r`` every head shares. The cache row is
  ``[c ; k_rope]`` (``cfg.latent_dim`` values).
- EXPANDED (:func:`latent_expand_kv`; the uncached forward, a chunk's own
  attention): ``[k_nope_h ; v_h] = c·W_kvb`` a head, ``s = scale·(q_nope_h·
  k_nope_h + q_rope_h·k_rope)``, ``o_h = Σ p·v_h``.
- ABSORBED (:func:`latent_absorb_q`, :func:`latent_expand_out`; every read
  of the cache): ``q̃_h = q_nope_h·W_UK_hᵀ`` in the latent space, ``s =
  scale·([q̃_h ; q_rope_h]·[c ; k_rope])``, ``õ_h = Σ p·c``, ``o_h = õ_h·
  W_UV_h``, with ``W_UK_h`` / ``W_UV_h`` the column blocks of ``W_kvb`` for
  head ``h``: the history is never expanded to heads. Equal in exact
  arithmetic; ``scale = cfg.attn_scale`` (YaRN's ``mscale²`` included).
- a sparse layer adds ``Shared(h)``, a SiLU-GLU every token takes, beside
  the routed experts (``cfg.shared_expert_size``; scope ``moe_shared``).

A PARALLEL block (``cfg.parallel_block``; Cohere2-MoE's, ``hf_loader``:
``cohere2_moe``) has ONE norm a layer and no ``ln2``: attention and the
experts both read the same ``h``, and nothing re-normalises the stream
between them:

- ``h = LayerNorm(x)``: ``(x − mean)·rsqrt(var + eps)·scale``, no bias, on
  the float32 stream (``cfg.norm == "layernorm"``);
- window layers: rotary on the whole head in INTERLEAVED pairs ``(2i,
  2i + 1)`` (``cfg.rope_interleaved``); full layers: NO positional term
  (``cfg.full_attn_rope`` False; ``rope_tables`` holds no table for them);
  every layer 16 query heads a KV head at the published widths;
- ``x ← x + Attn(h)·Wo + Experts(h) + Shared(h) / n``: ``n =
  cfg.shared_experts_averaged`` shared experts side by side in one GLU of
  ``shared_expert_size = n × width``, their outputs AVERAGED;
- final LayerNorm; ``logits = x·Eᵀ`` over the embedding's rows (a TIED head:
  the tree has no ``lm_head``; ``tf.lm_logits``).

**The residual stream is float32** whatever the parameters' dtype
(:func:`residual_stream`): the matmuls take the norms' outputs cast to the
compute dtype, their results are added in float32, and the router reads
its norm's float32 output. A top-8-of-256 selection flips when upstream
rounding moves a router logit past its neighbour: on the v5e the program's
eight differed from the float32 reference's in 11.6% of (token, layer)
selections with a bf16 stream and in 10.5-10.8% with this one (the bf16
matmuls' own rounding, 0.3-0.5% of every increment, is most of it: inherent
to bf16 serving: rounding K and V into a bf16 cache alone flips 4-6% in a
CPU replica at these widths), and the float32 stream was also the faster
(PERF.md, PR 31). The benchmark's reference therefore judges the tokens
whose routing its own margins decide (``benchmark/reference/
mimo_v2_decoder.py``).

This module is the uncached forward (``transformer.forward`` routes here)
and the pieces the paged engine shares with it (``engine_v2``)."""

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops import paged_attention as pa


def init_typed_params(cfg, rng: jax.Array, dtype=jnp.float32):
    """The parameter tree of a typed stack: ``embed``, ``layers`` (a LIST,
    one tree a layer: ``ln1``, ``attn`` {wq, wk, wv, wo, sink?} — a latent
    layer's {wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo} —, ``ln2``
    (a parallel block has none), and ``mlp`` {wg, wi, wo} or ``moe``
    {router, router_bias?, wg, wi, wo over the HELD experts} with, where
    the model has one, ``shared`` {wg, wi, wo}), ``final_norm``, and
    ``lm_head`` unless the head is tied to ``embed``."""
    if not cfg.is_glu or cfg.use_bias or cfg.ln_bias or \
            cfg.pos_emb != "rope" or \
            (cfg.parallel_block and cfg.parallel_block_norms != 1):
        raise NotImplementedError(
            "typed layer stacks are built for bias-free GLU decoders with "
            "rotary positions (or none on the full kind): RMSNorm or "
            "LayerNorm, a sequential block or a parallel one under ONE "
            "norm, a tied or an untied head (mimo_v2, deepseek_v3, "
            "cohere2_moe)")
    d, v, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    dk, dv, H = cfg.head_dim, cfg.v_dim, cfg.num_heads
    out_std = cfg.init_std / math.sqrt(2 * L)
    # (a layer draws at most 9 keys, 12 with latent attention or a shared
    # expert; the count is part of what a seed gives)
    draws = 13 if cfg.latent or cfg.shared_expert_size else 10
    keys = iter(jax.random.split(rng, draws * L + 2))

    def w(shape, std=cfg.init_std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std
                ).astype(dtype)

    layers = []
    for l, kind in enumerate(cfg.layer_kinds):
        kvh = cfg.kind_kv_heads(kind)
        if kind == 2:
            ql, kl, nope = cfg.q_lora_rank, cfg.kv_lora_rank, \
                cfg.qk_nope_head_dim
            inner = lambda r: {"scale": jnp.ones((r,), jnp.float32)}
            attn = {"wq_a": w((d, ql)), "q_norm": inner(ql),
                    "wq_b": w((ql, H * dk)),
                    "wkv_a": w((d, cfg.latent_dim)), "kv_norm": inner(kl),
                    "wkv_b": w((kl, H * (nope + dv))),
                    "wo": w((H * dv, d), out_std)}
        else:
            attn = {"wq": w((d, H * dk)), "wk": w((d, kvh * dk)),
                    "wv": w((d, kvh * dv)), "wo": w((H * dv, d), out_std)}
        if kind == 1 and cfg.window_sink:
            # not zero at init: a zero sink would make a test of it vacuous
            attn["sink"] = w((H,), 1.0)
        lp = {"ln1": tf._norm_params(cfg), "attn": attn}
        if not cfg.parallel_block:
            lp["ln2"] = tf._norm_params(cfg)
        if cfg.layer_is_sparse(l):
            E, held, f = cfg.num_experts, cfg.num_held_experts, cfg.ffn_size
            moe = {"router": w((d, E)), "wg": w((held, d, f)),
                   "wi": w((held, d, f)), "wo": w((held, f, d), out_std)}
            if cfg.router_select_bias:
                moe["router_bias"] = jnp.zeros((E,), dtype)
            lp["moe"] = moe
            if cfg.shared_expert_size:
                fs = cfg.shared_expert_size
                lp["shared"] = {"wg": w((d, fs)), "wi": w((d, fs)),
                                "wo": w((fs, d), out_std)}
        else:
            f = cfg.dense_intermediate_size or cfg.ffn_size
            lp["mlp"] = {"wg": w((d, f)), "wi": w((d, f)),
                         "wo": w((f, d), out_std)}
        layers.append(lp)
    params = {"embed": {"tokens": w((v, d))}, "layers": layers,
              "final_norm": tf._norm_params(cfg)}
    if not cfg.tie_embeddings:
        params["lm_head"] = w((d, v))
    return params


def rope_tables(cfg, positions: jax.Array) -> dict:
    """{kind: (sin, cos)} for the kinds the stack has: one table a rotary
    base, computed once a step; (None, None) for a kind without a
    positional term (``cfg.full_attn_rope`` False)."""
    tables = {}
    for kind in sorted(set(cfg.layer_kinds)):
        theta = cfg.kind_rope_theta(kind)
        tables[kind] = (None, None) if theta is None else tf.rope_table(
            dataclasses.replace(cfg, rope_theta=theta), positions)
    return tables


@jax.named_scope("attn_qkv")
def typed_qkv(cfg, kind: int, p, x: jax.Array, sin, cos
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [B, t, D] → q [B, t, H, Dk], k [B, t, KV_kind, Dk],
    v [B, t, KV_kind, Dv] (scaled), RoPE applied to q and k (``sin`` None:
    the kind has no positional term)."""
    b, t = x.shape[:2]
    kvh = cfg.kind_kv_heads(kind)
    q = tf.linear_2d(x, p, "wq").reshape(b, t, cfg.num_heads, cfg.head_dim)
    k = tf.linear_2d(x, p, "wk").reshape(b, t, kvh, cfg.head_dim)
    v = tf.linear_2d(x, p, "wv").reshape(b, t, kvh, cfg.v_dim)
    if cfg.value_scale != 1.0:
        v = (v * cfg.value_scale).astype(v.dtype)
    if sin is None:
        return q, k, v
    return tf.apply_rope(q, sin, cos, cfg.rope_interleaved), \
        tf.apply_rope(k, sin, cos, cfg.rope_interleaved), v


@jax.named_scope("attn_qkv")
def latent_qkv(cfg, p, x: jax.Array, sin, cos):
    """A latent layer's projections: x [B, t, D] → (q_nope [B, t, H, nope],
    q_rope [B, t, H, rope], latent [B, t, kv_lora + rope]): RoPE applied to
    q_rope and to the shared rotary key; ``latent`` = ``[RMSNorm(c_kv) ;
    k_rope]`` is the row the cache holds."""
    b, t = x.shape[:2]
    nope, kl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = tf._norm(cfg, p["q_norm"], tf.linear_2d(x, p, "wq_a"))
    q = tf.linear_2d(c_q, p, "wq_b").reshape(b, t, cfg.num_heads,
                                             cfg.head_dim)
    kv = tf.linear_2d(x, p, "wkv_a")
    c = tf._norm(cfg, p["kv_norm"], kv[..., :kl])
    k_rope = tf.apply_rope(kv[..., None, kl:], sin, cos)[..., 0, :]
    return q[..., :nope], tf.apply_rope(q[..., nope:], sin, cos), \
        jnp.concatenate([c, k_rope], axis=-1)


def _wkv_b(cfg, p) -> jax.Array:
    """``W_kvb`` as [kv_lora, H, nope + v]: ``[..., :nope]`` is ``W_UK``,
    the rest ``W_UV``."""
    return p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                              cfg.qk_nope_head_dim + cfg.v_dim)


@jax.named_scope("attn_latent")
def latent_absorb_q(cfg, p, q_nope: jax.Array, q_rope: jax.Array,
                    width: int) -> jax.Array:
    """The absorbed query: ``[q_nope_h·W_UK_hᵀ ; q_rope_h ; 0…]`` →
    [B, t, H, width] (``width`` the latent pool's lanes): its dot with a
    cached row is the expanded form's ``q_h·k_h``."""
    w_uk = _wkv_b(cfg, p)[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("bthd,lhd->bthl", q_nope, w_uk)
    return jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                   ((0, 0),) * 3 + ((0, width - cfg.latent_dim),))


@jax.named_scope("attn_latent")
def latent_expand_out(cfg, p, o_lat: jax.Array) -> jax.Array:
    """``õ_h·W_UV_h``: the weighted sum of latents [B, t, H, kv_lora] →
    the heads' outputs [B, t, H, v]."""
    w_uv = _wkv_b(cfg, p)[..., cfg.qk_nope_head_dim:]
    return jnp.einsum("bthl,lhv->bthv", o_lat, w_uv)


@jax.named_scope("attn_latent")
def latent_expand_kv(cfg, p, q_nope: jax.Array, q_rope: jax.Array,
                     latent: jax.Array):
    """The expanded form of a chunk's own tokens: (q [B, t, H, nope+rope],
    k [B, t, H, nope+rope] — ``c·W_UK`` a head beside the shared rotary key
    —, v [B, t, H, v])."""
    kl = cfg.kv_lora_rank
    kv = jnp.einsum("btl,lhd->bthd", latent[..., :kl], _wkv_b(cfg, p))
    k_rope = jnp.broadcast_to(latent[..., None, kl:], q_rope.shape)
    nope = cfg.qk_nope_head_dim
    return jnp.concatenate([q_nope, q_rope], axis=-1), \
        jnp.concatenate([kv[..., :nope], k_rope], axis=-1), kv[..., nope:]


@jax.named_scope("attn_out")
def typed_attn_out(cfg, p, out: jax.Array) -> jax.Array:
    b, t = out.shape[:2]
    return tf.linear_2d(out.reshape(b, t, cfg.num_heads * cfg.v_dim), p,
                        "wo")


def apply_sink(out: jax.Array, lse: jax.Array,
               sink: Optional[jax.Array]) -> jax.Array:
    """Let a sink column into a finished softmax: ``out`` [n, c, h, Dv]
    was normalised by ``exp(lse)`` ([n, c, h] float32); with the sink the
    denominator is ``exp(lse) + exp(sink_h)``, so the output shrinks by
    ``sigmoid(lse − sink_h)``. None → unchanged."""
    if sink is None:
        return out
    shrink = jax.nn.sigmoid(lse - sink.astype(jnp.float32))
    return (out.astype(jnp.float32) * shrink[..., None]).astype(out.dtype)


def residual_stream(x: jax.Array) -> Tuple[jax.Array, Any]:
    """(the embedding in float32, the compute dtype it came in)."""
    return x.astype(jnp.float32), x.dtype


def typed_ffn(cfg, lp, h: jax.Array, moe_fn: Optional[Callable],
              valid: Optional[jax.Array] = None, dtype=None) -> jax.Array:
    """The layer's second half on its normed input ``h`` (float32): the
    dense SiLU-GLU of a dense layer in the compute ``dtype``, or the
    experts (``moe_fn(cfg, p, x, valid=)``: the router reads ``h`` as it
    is, the experts cast it) and, where the layer has one, the shared
    expert every token takes (compute dtype; on every chip of an
    expert-parallel deployment, so it counts once across the shares)."""
    if "moe" not in lp:
        return tf._mlp(cfg, lp["mlp"], h.astype(dtype or h.dtype))
    from deepspeed_tpu.parallel import moe
    with jax.named_scope("moe"):
        out = (moe_fn or moe.held_experts_moe_layer)(
            cfg, lp["moe"], h, valid=valid)[0]
    if "shared" not in lp:
        return out
    with jax.named_scope("moe_shared"):
        hs = h.astype(dtype or h.dtype)
        shared = moe._shared_expert(
            lp["shared"], hs.reshape(-1, hs.shape[-1])).reshape(hs.shape)
        if cfg.shared_experts_averaged > 1:
            return out + shared.astype(jnp.float32) * \
                (1.0 / cfg.shared_experts_averaged)
        return out + shared


def block_residual(cfg, lp, x: jax.Array, h: jax.Array, attn_out: jax.Array,
                   moe_fn: Optional[Callable], valid, dtype) -> jax.Array:
    """The float32 stream after a layer, given ``h`` (the layer's first
    norm of ``x``, float32: what attention read) and the attention
    branch's output: sequential (``x + a``, then the feed-forward on
    ``norm2`` of that) or PARALLEL (``cfg.parallel_block``: the
    feed-forward reads the SAME ``h``, and both are added)."""
    if cfg.parallel_block:
        return x + attn_out + typed_ffn(cfg, lp, h, moe_fn, valid, dtype)
    x = x + attn_out
    return x + typed_ffn(cfg, lp, tf._norm(cfg, lp["ln2"], x), moe_fn, valid,
                         dtype)


def _attention(cfg, kind: int, sink, q, k, v) -> jax.Array:
    """Uncached attention of one layer: q [B, T, H, Dk], k [B, T, KV, Dk],
    v [B, T, KV, Dv] → [B, T, H, Dv]; causal, the kind's window, the
    sink."""
    out, lse = pa.causal_attention_with_lse(
        q, k, v, window=cfg.kind_window(kind), scale=cfg.attn_scale)
    return apply_sink(out, lse, sink)


def forward_hidden_typed(cfg, params, tokens: jax.Array,
                         moe_fn: Optional[Callable] = None,
                         positions: Optional[jax.Array] = None
                         ) -> jax.Array:
    """tokens [B, T] → final-norm hidden [B, T, D]; the layer loop
    unrolled over the list of typed layers."""
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x, dtype = residual_stream(
        tf.embed_tokens(cfg, params["embed"], tokens, positions))
    tables = rope_tables(cfg, positions)
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        h32 = tf._norm(cfg, lp["ln1"], x)
        h = h32.astype(dtype)
        if kind == 2:       # the expanded form: nothing is cached here
            q, k, v = latent_expand_kv(cfg, lp["attn"], *latent_qkv(
                cfg, lp["attn"], h, *tables[kind]))
        else:
            q, k, v = typed_qkv(cfg, kind, lp["attn"], h, *tables[kind])
        with jax.named_scope("attn_core"):
            o = _attention(cfg, kind, lp["attn"].get("sink"), q, k, v)
        x = block_residual(cfg, lp, x, h32,
                           typed_attn_out(cfg, lp["attn"], o), moe_fn, None,
                           dtype)
    return tf._norm(cfg, params["final_norm"], x).astype(dtype)
