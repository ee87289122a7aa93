"""Mamba-2 state-space mixer: the pieces a typed layer stack's kind-3
layers are made of (``models/typed_layers.py`` has the equations, the
uncached forward and the parameter tree; ``inference/engine_v2.py`` the
served form over the state pools).

Plain ``jax.numpy``. What works on a token alone (the input and output
projections, the gated norm) takes any leading shape; the convolution and
the scan take ROWS ``[m, c, ...]`` — ``m`` sequences, ``c`` positions each,
row ``r``'s first ``counts[r]`` live — with what each row CARRIES: its
state ``S [m, H, P, N]`` float32 and the last ``K − 1`` inputs of its
convolution ``[m, K − 1, d + 2GN]``. A position past ``counts`` advances
nothing: its ``Δ`` is 0 (decay 1, no input) and the carried tail skips it.

Two forms of ONE scan, picked by the row's width (a shape, not an option):

- :func:`scan_chunk` (``c > 1``): the chunk form. With ``cum_t = Σ_{s≤t}
  Δ_s·A`` a head, ``Y = (L ∘ C·Bᵀ)·(Δ·x) + exp(cum)·C·S_in + D·x`` where
  ``L[t, s] = exp(cum_t − cum_s)`` for ``s ≤ t``, and ``S_out =
  exp(cum_end)·S_in + Σ_t exp(cum_end − cum_t)·Δ_t·x_t ⊗ B_t``: matmuls of
  the chunk's width, the carried state read once and written once.
- :func:`scan_step` (``c == 1``): the recurrence ``S ← a·S + Δ·x ⊗ B``,
  ``y = S·C + D·x``, elementwise in float32: bound by the state's bytes.

The state pools (:func:`init_state_pools`) hold a slot a sequence, ONE
pool a state-space layer: a launch's one-token pass rewrites a layer's
whole pool, and a buffer of its own bounds what the compiler may copy."""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


#: the state pools' names in an engine's arena, beside the KV pools': the
#: state ``ssm<i>`` and the convolution's carried inputs ``conv<i>`` of the
#: ``i``-th state-space layer (:func:`pool_names`)
STATE_POOLS = ("ssm", "conv")


def pool_names(i: int) -> Tuple[str, str]:
    """The arena's names of the ``i``-th state-space layer's two pools."""
    return tuple(f"{kind}{i}" for kind in STATE_POOLS)


def is_state_pool(name: str) -> bool:
    """An arena entry's name is one of :func:`pool_names`'."""
    return name not in STATE_POOLS and \
        name.rstrip("0123456789") in STATE_POOLS


def init_state_pools(cfg, slots: int, dtype) -> Dict[str, jax.Array]:
    """``{"ssm<i>": [slots + 1, H, P, N] float32, "conv<i>": [slots + 1,
    (K − 1)·(d + 2GN)] dtype}`` for the ``i``-th of the state-space layers
    of ``cfg``: sequence slot ``s`` at row ``s``, the last row the trash
    (padding rows of a step). A POOL A LAYER, not one flat pool as the KV
    pools are: a step's one-token pass rewrites a layer's whole pool, and
    where the compiler cannot show an update to be in place it copies the
    buffer the update is made on — 2.3 GB a layer of a flat pool at Granite
    4.0-H's nine layers of 4 MiB states (found on the v5e, PR 45: the
    64-row split program asked for 16.2 GB), one layer's 0.27 GB here. A
    slot's ``K − 1`` convolution inputs lie side by side in one row (a
    dimension of 3 would be padded to a tile of 8, and the compiler relaid
    the pool on its way in and out of every program)."""
    pools = {}
    for i in range(sum(1 for kind in cfg.layer_kinds if kind == 3)):
        state, conv = pool_names(i)
        pools[state] = jnp.zeros((slots + 1, cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state_size), jnp.float32)
        pools[conv] = jnp.zeros((slots + 1, (cfg.ssm_conv_kernel - 1) *
                                 cfg.ssm_conv_dim), dtype)
    return pools


def fresh_rows(starts: jax.Array) -> jax.Array:
    """[n] bool: the rows that start at position 0. What they carry in is
    ZERO whatever their slot held before: a slot is reused and never
    cleaned, so the PROGRAM resets it (:func:`carried`, ``scan_step``'s
    ``reset``)."""
    return starts == 0


def carried(held: jax.Array, reset: jax.Array) -> jax.Array:
    """What rows carry IN: what the pool ``held`` [m, ...] for them, and
    zero for a row that starts at position 0 (``reset`` [m])."""
    return jnp.where(reset.reshape((-1,) + (1,) * (held.ndim - 1)), 0, held)


def tail_rows(cfg, held: jax.Array) -> jax.Array:
    """Rows of the ``conv`` pool [m, (K − 1)·Cd] → tails [m, K − 1, Cd]."""
    return held.reshape(held.shape[0], cfg.ssm_conv_kernel - 1, -1)


def split_in(cfg, zxbcdt: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The input projection's columns ``[z | xBC | dt]``: widths ``d``,
    ``d + 2GN``, ``H``."""
    d, cd = cfg.ssm_inner, cfg.ssm_conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + cd], zxbcdt[..., d + cd:]


def conv_rows(cfg, p, xbc: jax.Array, tail: jax.Array, counts: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """The causal depthwise convolution over time, then SiLU: xbc
    [m, c, Cd] after the rows' carried ``tail`` [m, K − 1, Cd] → (u
    [m, c, Cd], the tail each row carries on: the ``K − 1`` inputs that end
    at its last live position; a row with no live position keeps its
    own)."""
    k = cfg.ssm_conv_kernel
    c = xbc.shape[1]
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = p["conv_w"].astype(jnp.float32)                       # [Cd, K]
    acc = p["conv_b"].astype(jnp.float32)
    for i in range(k):      # u_t = Σ_i w[:, i]·seq[t + i] (seq[t + K − 1]
        acc = acc + seq[:, i:i + c].astype(jnp.float32) * w[:, i]   # is x_t)
    at = counts[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    return jax.nn.silu(acc).astype(xbc.dtype), \
        jnp.take_along_axis(seq, at[..., None], axis=1)


def _heads(cfg, u: jax.Array, dt: jax.Array, p, counts: jax.Array):
    """u [m, c, Cd], dt [m, c, H] → x [m, c, G, Hg, P], B, C [m, c, G, N]
    (compute dtype), Δ [m, c, G, Hg] float32 (0 past a row's ``counts``),
    and ``A``, ``D`` [G, Hg] float32."""
    m, c = u.shape[:2]
    g, n, h, hd = cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_heads, \
        cfg.ssm_head_dim
    d = cfg.ssm_inner
    x = u[..., :d].reshape(m, c, g, h // g, hd)
    b = u[..., d:d + g * n].reshape(m, c, g, n)
    cc = u[..., d + g * n:].reshape(m, c, g, n)
    live = jnp.arange(c, dtype=jnp.int32)[None] < counts[:, None]
    delta = jax.nn.softplus(dt.astype(jnp.float32) +
                            p["dt_bias"].astype(jnp.float32))
    delta = jnp.where(live[..., None], delta, 0.0).reshape(m, c, g, h // g)
    a = -jnp.exp(p["A_log"].astype(jnp.float32)).reshape(g, h // g)
    return x, b, cc, delta, a, p["D"].astype(jnp.float32).reshape(g, h // g)


def scan_step(cfg, p, u: jax.Array, dt: jax.Array, state: jax.Array,
              counts: jax.Array, reset=None) -> Tuple[jax.Array, jax.Array]:
    """The recurrence on rows of ONE position: u [m, 1, Cd], dt [m, 1, H],
    state [m, H, P, N] float32, counts [m] (1 live, 0 not) → (y [m, 1, d]
    float32, the state after it). ``reset`` [m] bool: rows that start from
    ZERO whatever ``state`` holds for them — folded into the decay (``a =
    0``: a stale state is finite), so that a pool's region is read once and
    written once and not passed over a second time to zero a few slots."""
    m = u.shape[0]
    g, n, hd = cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_head_dim
    x, b, c, delta, a, skip = _heads(cfg, u, dt, p, counts)
    x, delta = x[:, 0].astype(jnp.float32), delta[:, 0]    # [m, G, Hg(, P)]
    b, c = (t[:, 0].astype(jnp.float32)[:, :, None, None, :]
            for t in (b, c))                                # [m, G, 1, 1, N]
    decay = jnp.exp(delta * a)
    if reset is not None:
        decay = jnp.where(reset[:, None, None], 0.0, decay)
    s = state.reshape(m, g, -1, hd, n)
    s = decay[..., None, None] * s + (delta[..., None] * x)[..., None] * b
    y = jnp.sum(s * c, axis=-1) + skip[..., None] * x
    return y.reshape(m, 1, cfg.ssm_inner), s.reshape(state.shape)


def scan_chunk(cfg, p, u: jax.Array, dt: jax.Array, state: jax.Array,
               counts: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The chunk form on rows of ``c`` positions: u [m, c, Cd], dt
    [m, c, H], state [m, H, P, N] float32 (what the rows carried in),
    counts [m] → (y [m, c, d] float32, the state after each row's last
    live position). The chunk's matmuls take operands in the compute dtype
    and accumulate in float32; what touches the carried state is float32
    at ``Precision.HIGHEST`` (on a TPU a float32 matmul is otherwise bf16
    passes: the state would be read as if it were held in bf16)."""
    m, c = u.shape[:2]
    g, n, hd = cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_head_dim
    f32, dtype = jnp.float32, u.dtype
    x, b, cc, delta, a, skip = _heads(cfg, u, dt, p, counts)
    cum = jnp.cumsum(delta * a, axis=1)                     # [m, c, G, Hg]
    t = jnp.arange(c, dtype=jnp.int32)
    # L[t, s] = Π_{s < r ≤ t} a_r for s ≤ t: masked BEFORE the exponential
    seg = cum[:, :, None] - cum[:, None]                    # [m, t, s, ..]
    decay = jnp.exp(jnp.where((t[:, None] >= t[None])[None, :, :, None,
                                                      None], seg, -jnp.inf))
    cb = jnp.einsum("mtgn,msgn->mtsg", cc, b, preferred_element_type=f32)
    dx = delta[..., None] * x.astype(f32)                   # [m,c,G,Hg,P]
    y = jnp.einsum("mtsgh,msghp->mtghp",
                   (decay * cb[..., None]).astype(dtype), dx.astype(dtype),
                   preferred_element_type=f32)
    s_in = state.reshape(m, g, -1, hd, n)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "mtgn,mghpn->mtghp", cc.astype(f32), s_in,
        precision=lax.Precision.HIGHEST)
    y = y + skip[..., None] * x.astype(f32)
    to_end = jnp.exp(cum[:, -1:] - cum)                     # [m, c, G, Hg]
    s_out = jnp.exp(cum[:, -1])[..., None, None] * s_in + jnp.einsum(
        "mtghp,mtgn->mghpn", (dx * to_end[..., None]).astype(dtype), b,
        preferred_element_type=f32)
    return y.reshape(m, c, cfg.ssm_inner), s_out.reshape(state.shape)


def scan_rows(cfg, p, u, dt, state, counts):
    """The scan in the form the rows' width picks."""
    scan = scan_step if u.shape[1] == 1 else scan_chunk
    return scan(cfg, p, u, dt, state, counts)


def gated_norm(cfg, p, y: jax.Array, z: jax.Array, dtype) -> jax.Array:
    """``w ⊙ GroupRMS(y ⊙ silu(z))``: the gate BEFORE the norm, the norm in
    ``G`` groups of ``d / G``, float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(gated.shape[:-1] + (cfg.ssm_groups, -1))
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * lax.rsqrt(var + cfg.norm_eps)).reshape(gated.shape)
    return (normed * p["norm"]["scale"].astype(jnp.float32)).astype(dtype)
