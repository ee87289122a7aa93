"""The plain reference for the dense decoders the benchmark runs
(Mistral-7B-v0.1 and anything with the same block): RMSNorm → GQA
attention with rotary positions, causal + sliding-window mask → residual →
RMSNorm → SiLU-GLU → residual; final RMSNorm; untied output head;
next-token cross-entropy. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes). No kernels, no cache, no batching: one
sequence at a time, one layer's weights cast to float32 at a time.

It follows the published description (HF ``modeling_mistral.py``):
rotate-half RoPE with ``inv_freq = theta ** (-2i / head_dim)``, query head
``h`` reads KV head ``h // (heads / kv_heads)``, scores scaled by
``head_dim ** -0.5``, key ``j`` visible to query ``i`` when
``0 <= i - j < sliding_window``. Departures: attention is computed in
blocks of ``QUERY_BLOCK`` queries against all keys (same arithmetic, less
memory); weights arrive in the program's tree layout (``[in, out]``
matrices stacked over layers), which this file reads and nothing else.

Independent of the code under test: it imports nothing from
``deepspeed_tpu``.

**The reference contract.** A configuration file names its reference
module (``"reference": "<module>"`` finds ``benchmark/reference/<module>.py``;
without the key, this one). The runners use these four names of it and
nothing else, so a new architecture is a new file here:

- ``Widths.from_hf(hf)``: the sizes, from the FILE's published keys
  (hashable, so a jitted layer can take it as a static argument);
- ``matmul_params_per_token(w)``: the matmul parameters one token
  multiplies, forward, whatever the block's mathematics makes them (for
  sparse experts: the experts a token is routed to, and the router) — the
  numerator of ``mfu``, kept with the benchmark;
- ``loss(w, params, batch, device)``: the loss the trainer reports for a
  ``[B, T]`` batch at the program's parameter tree, as a float;
- ``argmax_gaps(w, params, prompts, outputs, device)``: for every generated
  token of every request, flattened, how far this reference scores it
  below its own argmax at that position.

float32 under ``jax.default_matmul_precision("highest")``, inputs from the
seed and the program's parameter tree only. The limits that decide
``correct`` are the runners' and the same for every reference."""

from dataclasses import dataclass
from functools import partial
from types import SimpleNamespace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops

QUERY_BLOCK = 512


@dataclass(frozen=True)
class Widths:
    """The sizes the reference needs, read from the configuration FILE
    (published ``config.json`` keys), not from the program's model
    object."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    layers: int
    eps: float
    theta: float
    window: Optional[int]

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        heads = int(hf["num_attention_heads"])
        return cls(hidden=int(hf["hidden_size"]), heads=heads,
                   kv_heads=int(hf.get("num_key_value_heads", heads)),
                   head_dim=int(hf.get("head_dim") or
                                hf["hidden_size"] // heads),
                   ffn=int(hf["intermediate_size"]),
                   vocab=int(hf["vocab_size"]),
                   layers=int(hf["num_hidden_layers"]),
                   eps=float(hf["rms_norm_eps"]),
                   theta=float(hf["rope_theta"]),
                   window=hf.get("sliding_window"))


def matmul_params_per_token(w: Widths) -> int:
    """This block's count is the dense one ``lib/flops.py`` keeps: the
    layers' projections and one GLU each, and the untied output head."""
    return flops.matmul_params(SimpleNamespace(
        hidden_size=w.hidden, num_heads=w.heads, kv_heads=w.kv_heads,
        head_dim=w.head_dim, intermediate_size=w.ffn, num_layers=w.layers,
        vocab_size=w.vocab))


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [T, H, Dh]; rotate-half."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window):
    """q [T, H, Dh], k/v [T, KvH, Dh] → [T, H, Dh]; T a multiple of the
    query block."""
    t, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    blk = min(t, QUERY_BLOCK)
    kpos = jnp.arange(t)

    def block(args):
        qb, start = args
        qpos = start + jnp.arange(blk)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (dh ** -0.5)
        dist = qpos[:, None] - kpos[None, :]
        ok = dist >= 0
        if window is not None:
            ok = ok & (dist < window)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(t // blk, blk, h, dh),
                              jnp.arange(0, t, blk)))
    return out.reshape(t, h, dh)


def attention_block(x, lp, w: Widths):
    """The block's first half on one sequence: x [T, D] float32 →
    x + attention(RMSNorm(x))."""
    t = x.shape[0]
    pos = jnp.arange(t)
    a = lp["attn"]
    hin = _rms_norm(x, lp["ln1"]["scale"], w.eps)
    q = (hin @ a["wq"]).reshape(t, w.heads, w.head_dim)
    k = (hin @ a["wk"]).reshape(t, w.kv_heads, w.head_dim)
    v = (hin @ a["wv"]).reshape(t, w.kv_heads, w.head_dim)
    q, k = _rope(q, pos, w.theta), _rope(k, pos, w.theta)
    o = _attention(q, k, v, w.window).reshape(t, w.heads * w.head_dim)
    return x + o @ a["wo"]


@partial(jax.jit, static_argnames=("w",))
def _layer(x, lp, w: Widths):
    """One decoder block on one sequence. x [T, D] float32."""
    x = attention_block(x, lp, w)
    m = lp["mlp"]
    hin = _rms_norm(x, lp["ln2"]["scale"], w.eps)
    return x + (jax.nn.silu(hin @ m["wg"]) * (hin @ m["wi"])) @ m["wo"]


@partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, lm_head, eps):
    return _rms_norm(x, scale, eps) @ lm_head


def _f32(tree, device, index=None):
    def one(a):
        a = a if index is None else a[index]
        return jax.device_put(a, device).astype(jnp.float32)
    return jax.tree.map(one, tree)


def _pow2_at_least(n: int, floor: int) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


def _padded(tokens: Sequence[int]) -> np.ndarray:
    """Right-pad to a power of two of at least one query block (few
    distinct shapes to compile; a causal mask makes the tail harmless)."""
    out = np.zeros(_pow2_at_least(len(tokens), QUERY_BLOCK), np.int32)
    out[:len(tokens)] = tokens
    return out


def final_hidden(w: Widths, params, token_rows: List[np.ndarray], device,
                 layer=_layer):
    """Last-layer hidden states, one [T, D] float32 array per sequence, all
    on ``device`` (sharded parameters are gathered to it a layer at a
    time). Layer-major: each layer's weights are cast once and used for
    every sequence. Spreading the sequences over four chips was tried and
    was slower (four copies of every layer to move and cast: 55 s against
    27 s for 8 x 4096 tokens through 16 layers; my chip run, PR 27).
    ``layer(x, lp, w)`` is the block: another architecture's reference
    that shares this file's embedding, head and loss hands its own in."""
    emb = params["embed"]["tokens"]
    xs = [jax.device_put(emb[jnp.asarray(r)], device).astype(jnp.float32)
          for r in token_rows]
    with jax.default_matmul_precision("highest"):
        for i in range(w.layers):
            lp = _f32(params["layers"], device, i)
            xs = [layer(x, lp, w) for x in xs]
            del lp
    return xs


def loss(w: Widths, params, batch: np.ndarray, device,
         layer=_layer) -> float:
    """Mean next-token cross-entropy over a [B, T] batch (every position
    but each row's last), as the trainer defines its loss."""
    rows = [np.asarray(r, np.int32) for r in batch]
    xs = final_hidden(w, params, rows, device, layer)
    scale = _f32(params["final_norm"]["scale"], device)
    head = _f32(params["lm_head"], device)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for r, x in zip(rows, xs):
            logits = _head(x, scale, head, w.eps)[:-1]
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, jnp.asarray(r[1:])[:, None], axis=-1)[:, 0]
            # fetched row by row: one row's [T, V] float32 logits at a time
            total += float(jnp.sum(nll))
    return total / sum(len(r) - 1 for r in rows)


def argmax_gaps(w: Widths, params, prompts, outputs, device,
                layer=_layer) -> np.ndarray:
    """Teacher-forced check of generated tokens: for every generated token
    of every request (flattened), how far the reference scores it below
    its own argmax at that position (0.0: it IS the argmax)."""
    rows = [_padded(list(p) + list(o)) for p, o in zip(prompts, outputs)]
    xs = final_hidden(w, params, rows, device, layer)
    scale = _f32(params["final_norm"]["scale"], device)
    head = _f32(params["lm_head"], device)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for p, o, x in zip(prompts, outputs, xs):
            # logits at position len(p)-1+j predict generated token j
            at = np.zeros(_pow2_at_least(len(o), 64), np.int32)
            at[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
            logits = np.asarray(_head(x[at], scale, head, w.eps))[:len(o)]
            gaps.append(logits.max(axis=-1) -
                        logits[np.arange(len(o)), np.asarray(o)])
    return np.concatenate(gaps)
