"""State-space mixers: the pieces a typed layer stack's kind-3 (Mamba-2)
and kind-4 (Mamba-1, the SELECTIVE scan; the last section) layers are made
of (``models/typed_layers.py`` has the equations, the uncached forward and
the parameter trees; ``inference/engine_v2.py`` the served form over the
state pools). What follows describes Mamba-2; a selective scan shares the
pools, the carry and the convolution, and has its own split, scan and gate
in its section (``typed_layers.mixer_forms`` picks by the layer's kind).

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
whole pool, and a buffer of its own bounds what the compiler may copy.

A GATED SHORT CONVOLUTION (kind 5; LFM2's mixer) is the convolution alone —
:func:`conv_rows` with no bias and no activation, between two gates that act
on a token alone (``typed_layers.short_conv_in`` / ``short_conv_out``) — and
carries its tail and nothing else: its layer has a ``conv<i>`` pool and no
``ssm<i>`` (:func:`init_state_pools`), the same slots and resets.

A GATED DELTA RULE (kind 6; Qwen3-Next's linear attention; the file's last
section) carries a MATRIX a value head, ``S [d_k, d_v]`` float32, that a step
decays and then CORRECTS: it reads ``Sᵀk`` before it writes, so neither form
above computes it. It shares the pools, slots, resets and the convolution
(:func:`conv_rows` with no bias AND a SiLU) and has its own two forms,
:func:`delta_step` and :func:`delta_chunk`, and the gated norm with the gate
AFTER the norm (:func:`gated_norm`)."""

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
    of ``cfg`` (a selective scan's: :func:`state_shape`, and ``(K − 1)·d``
    inputs): sequence slot ``s`` at row ``s``, the last row the trash
    (padding rows of a step). A POOL A LAYER, not one flat pool as the KV
    pools are: a step's one-token pass rewrites a layer's whole pool, and
    where the compiler cannot show an update to be in place it copies the
    buffer the update is made on — 2.3 GB a layer of a flat pool at Granite
    4.0-H's nine layers of 4 MiB states (found on the v5e, PR 45: the
    64-row split program asked for 16.2 GB), one layer's 0.27 GB here. A
    slot's ``K − 1`` convolution inputs lie side by side in one row (a
    dimension of 3 would be padded to a tile of 8, and the compiler relaid
    the pool on its way in and out of every program). A gated short
    convolution (kind 5) has no state: its layer gets ``conv<i>`` alone. A
    gated delta rule's carried inputs are FLOAT32 whatever ``dtype``: its
    convolution reads ``[q | k | v]`` unrounded
    (``typed_layers._linear_wide`` says why)."""
    if cfg.delta_rule:
        dtype = jnp.float32
    pools = {}
    for i in range(sum(1 for kind in cfg.layer_kinds
                       if kind in (3, 4, 5, 6))):
        state, conv = pool_names(i)
        if not cfg.short_conv:
            pools[state] = jnp.zeros((slots + 1,) + state_shape(cfg),
                                     jnp.float32)
        pools[conv] = jnp.zeros((slots + 1, (cfg.ssm_conv_kernel - 1) *
                                 cfg.ssm_conv_dim), dtype)
    return pools


def state_shape(cfg) -> Tuple[int, ...]:
    """What ONE sequence carries in a state-space layer, float32: ``[H, P,
    N]`` (Mamba-2), or a selective scan's ``[N, d]`` — the channels on the
    lanes, 40 tiles of 128 at Jamba2-3B's 5,120, and not its 16 states; a
    gated delta rule's ``[H_v, d_k, d_v]`` (the value head's dims on the
    lanes); a gated short convolution carries none (an empty carry)."""
    if cfg.short_conv:
        return (0,)
    if cfg.delta_rule:
        return (cfg.ssm_heads, cfg.ssm_state_size, cfg.ssm_head_dim)
    if cfg.selective:
        return (cfg.ssm_state_size, cfg.ssm_inner)
    return (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size)


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


def conv_rows(cfg, p, xbc: jax.Array, tail: jax.Array, counts: jax.Array,
              dtype=None, silu: bool = True) -> Tuple[jax.Array, jax.Array]:
    """The causal depthwise convolution over time, then (``silu``) SiLU:
    xbc [m, c, Cd] after the rows' carried ``tail`` [m, K − 1, Cd] → (u
    [m, c, Cd] in ``dtype`` — None: xbc's —, the tail each row carries on:
    the ``K − 1`` inputs that end at its last live position; a row with no
    live position keeps its own). Whether the taps' sum passes a SiLU is
    the mixer KIND's to say (``typed_layers.MixerForms.conv_silu``: kinds
    3, 4 and 6 do, a gated short convolution's taps stand alone — at ``c
    == 1`` its ``K`` multiply-adds), not the tree's: a bias is added where
    the tree holds one (``conv_b``; kinds 5 and 6 have none), and a gated
    delta rule's convolution has no bias AND an activation."""
    k = cfg.ssm_conv_kernel
    c = xbc.shape[1]
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = p["conv_w"].astype(jnp.float32)                       # [Cd, K]
    acc = p["conv_b"].astype(jnp.float32) if "conv_b" in p else 0.0
    for i in range(k):      # u_t = Σ_i w[:, i]·seq[t + i] (seq[t + K − 1]
        acc = acc + seq[:, i:i + c].astype(jnp.float32) * w[:, i]   # is x_t)
    at = counts[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    return (jax.nn.silu(acc) if silu else acc).astype(dtype or xbc.dtype), \
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


def gated_norm(cfg, p, y: jax.Array, z: jax.Array, dtype, groups: int,
               gate_first: bool) -> jax.Array:
    """The mixer's gated RMS norm in ``groups`` groups of ``d / groups``,
    float32, the gate on the side the mixer KIND says (the caller's, not
    the tree's). ``gate_first`` (Mamba-2: ``groups = G``): ``w ⊙ GroupRMS(y
    ⊙ silu(z))``, ``w`` of ``d``. Else (a gated delta rule: ``groups =
    H_v``, a norm a value head): ``w ⊙ GroupRMS(y) ⊙ silu(z)`` — the norm
    FIRST, then the gate —, ``w`` of ONE group's width, shared by the
    groups."""
    y = y.astype(jnp.float32)
    gate = jax.nn.silu(z.astype(jnp.float32))
    if gate_first:
        y = y * gate
    grouped = y.reshape(y.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = grouped * lax.rsqrt(var + cfg.norm_eps)
    scale = p["norm"]["scale"].astype(jnp.float32)
    if gate_first:
        return (normed.reshape(y.shape) * scale).astype(dtype)
    return ((normed * scale).reshape(y.shape) * gate).astype(dtype)


# ---------------------------------------------------------------------------
# The selective scan (Mamba-1; kind 4)
# ---------------------------------------------------------------------------
#
# ``d`` channels of ``N`` states with NO heads: the decay is ``exp(Δ_t[d] ·
# A[n, d])``, a value a channel AND state, so a chunk is not a masked matmul
# (``L[t, s]`` would be a matrix a channel and state) and both forms are the
# recurrence itself, elementwise in float32 on ``S [m, N, d]``:
#
#     S_t = exp(Δ_t ⊗ A) ∘ S_{t−1} + (Δ_t ∘ u_t) ⊗ B_t ;  y_t = Σ_n S_t ∘ C_t
#                                                               + D ∘ u_t
#
# with ``Δ_t [d]``, ``B_t``, ``C_t [N]`` from ``tl.ssm_select`` (that triple
# stands where the functions above take ``dt``). The chunk form carries ``S``
# through the chunk's positions and never lays ``[c, N, d]`` out.

def selective_split(cfg, xz: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The input projection's columns ``[x′ | z]`` (the published module's
    order), ``d`` each → (z, x′)."""
    d = cfg.ssm_inner
    return xz[..., d:], xz[..., :d]


def selective_gate(y: jax.Array, z: jax.Array, dtype) -> jax.Array:
    """``y ⊙ silu(z)`` in float32: the mixer has a gate and NO norm."""
    return (y.astype(jnp.float32) *
            jax.nn.silu(z.astype(jnp.float32))).astype(dtype)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis, float32 (the mixer's INNER norms: under
    the caller's scope, not ``norm``)."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale.astype(jnp.float32)


def select_norms(cfg, p, dbc: jax.Array, dtype):
    """``u·W_x``'s columns ``[δ | B | C]`` (widths ``R``, ``N``, ``N``),
    each under its own RMSNorm → (δ in ``dtype``: ``W_dt``'s input; B, C
    float32)."""
    r, n = cfg.ssm_dt_rank, cfg.ssm_state_size
    eps = cfg.norm_eps
    return _rms(dbc[..., :r], p["dt_norm"]["scale"], eps).astype(dtype), \
        _rms(dbc[..., r:r + n], p["b_norm"]["scale"], eps), \
        _rms(dbc[..., r + n:], p["c_norm"]["scale"], eps)


def step_sizes(p, dt: jax.Array, counts: jax.Array) -> jax.Array:
    """``Δ = softplus(dt + dt_bias)`` [m, c, d] float32, 0 past a row's
    ``counts`` (decay 1, no input: the position advances nothing)."""
    live = jnp.arange(dt.shape[1], dtype=jnp.int32)[None] < counts[:, None]
    delta = jax.nn.softplus(dt.astype(jnp.float32) +
                            p["dt_bias"].astype(jnp.float32))
    return jnp.where(live[..., None], delta, 0.0)


def _selective_update(p, s, delta, du, b, c, reset=None):
    """One position of every row: ``s`` [m, N, d], ``delta`` and ``du = Δ∘u``
    [m, d], ``b`` and ``c`` [m, N] → (the state after it, ``Σ_n S∘C`` [m,
    d]), float32."""
    decay = jnp.exp(delta[:, None, :] *
                    -jnp.exp(p["A_log"].astype(jnp.float32)))
    if reset is not None:
        decay = jnp.where(reset[:, None, None], 0.0, decay)
    s = decay * s + du[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def selective_step(cfg, p, u, sel, state, counts, reset=None):
    """:func:`scan_step`'s part for a selective scan: u [m, 1, d], ``sel``
    = (Δ [m, 1, d], B, C [m, 1, N]) of ``tl.ssm_select``, state [m, N,
    d]."""
    delta, b, c = (t[:, 0] for t in sel)
    x = u[:, 0].astype(jnp.float32)
    state, y = _selective_update(p, state, delta, delta * x, b, c, reset)
    return (y + p["D"].astype(jnp.float32) * x)[:, None], state


def selective_chunk(cfg, p, u, sel, state, counts, kernel: bool = False):
    """:func:`scan_chunk`'s part for a selective scan: u [m, c, d], ``sel`` = (Δ
    [m, c, d] — 0 past ``counts`` —, B, C [m, c, N]), state [m, N, d] →
    (y [m, c, d] float32, the state after each row's last live position).
    ``kernel``: the Pallas kernel (:func:`selective_scan_kernel`: the state
    in registers from the chunk's first position to its last live one, read
    from HBM once and written once); else an XLA loop over the positions
    with the state as its carry, which goes through HBM at every position
    (the CPU's form, and any width that is not whole lane tiles)."""
    delta, b, c = sel
    x = u.astype(jnp.float32)
    if kernel and x.shape[-1] % 128 == 0:
        return selective_scan_kernel(
            -jnp.exp(p["A_log"].astype(jnp.float32)),
            p["D"].astype(jnp.float32), x, delta, b, c, state, counts)

    def position(s, inp):
        return _selective_update(p, s, *inp)

    state, y = lax.scan(position, state, tuple(
        t.swapaxes(0, 1) for t in (delta, delta * x, b, c)))
    return y.swapaxes(0, 1) + p["D"].astype(jnp.float32) * x, state


def _selective_scan_body(counts_ref, delta_ref, x_ref, b_ref, c_ref, a_ref,
                         d_ref, s_ref, y_ref, so_ref):
    """Grid (rows, channel blocks): ONE program carries a row's state of
    ``N`` tiles ``[sb, 128]`` — ``sb x 128`` channels, one vector register a
    state index at ``sb`` 8 — through the row's LIVE positions
    (``counts_ref``, scalar-prefetched: a row riding along with one live
    token takes one turn, not the chunk's 128). ``delta_ref`` / ``x_ref`` /
    ``y_ref``: ``[1, c, sb, 128]`` blocks (a position is a leading index);
    ``b_ref`` / ``c_ref``: the row's ``B`` and ``C`` in SMEM, ``[1, 1, c·N]``
    — a position's ``B_t[n]`` is a SCALAR times a register, where a layout
    with the states on the sublanes would need it broadcast along the
    lanes —; ``a_ref`` ``[N, sb, 128]`` (``A`` itself, negative), ``d_ref``
    ``[sb, 128]``; ``s_ref`` / ``so_ref`` ``[1, N, sb, 128]``, aliased. A
    position past the live ones gets ``y = 0``."""
    n_state = a_ref.shape[0]
    a = [a_ref[n] for n in range(n_state)]
    skip = d_ref[...]
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def position(t, s):
        delta, x = delta_ref[0, t], x_ref[0, t]
        du, y = delta * x, skip * x
        out = []
        for n in range(n_state):
            s_n = jnp.exp(delta * a[n]) * s[n] + \
                du * b_ref[0, 0, t * n_state + n]
            y = y + s_n * c_ref[0, 0, t * n_state + n]
            out.append(s_n)
        y_ref[0, t] = y
        return tuple(out)

    s = lax.fori_loop(0, counts_ref[pl.program_id(0)], position,
                      tuple(s_ref[0, n] for n in range(n_state)))
    for n in range(n_state):
        so_ref[0, n] = s[n]


# jitted (as ``paged_attention._paged_call`` is): a program's unrolled
# layers call it once a layer and instance, and share ONE trace of the body
@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(a, skip, x, delta, b, c, state, counts,
                          interpret: bool = False):
    """The selective scan's chunk form as a Pallas kernel
    (:func:`_selective_scan_body`; its name in a device trace is
    ``selective_scan``): ``a`` [N, d] (= ``−exp(A_log)``), ``skip`` [d], x,
    delta [m, c, d], b, c [m, c, N], state [m, N, d], all float32, counts
    [m] → (y [m, c, d], the state after each row's last live position). The
    channels go to the kernel as ``[d / 128, 128]`` tiles, so ``d`` is a
    multiple of 128; a program holds 1,024 of them (8 sublanes) where
    ``d / 128`` is a multiple of 8, else all. HBM traffic: delta, x and y
    once each, the state in once and out once."""
    m, ch, d = x.shape
    n = state.shape[1]
    dt = d // 128
    sb = 8 if dt % 8 == 0 else dt

    def tiles(t):
        return t.reshape(t.shape[:-1] + (dt, 128))

    rows = pl.BlockSpec((1, ch, sb, 128), lambda i, j, counts: (i, 0, j, 0))
    scalars = pl.BlockSpec((1, 1, ch * n), lambda i, j, counts: (i, 0, 0),
                           memory_space=pltpu.SMEM)
    held = pl.BlockSpec((1, n, sb, 128), lambda i, j, counts: (i, 0, j, 0))
    y, state = pl.pallas_call(
        _selective_scan_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(m, dt // sb),
            in_specs=[rows, rows, scalars, scalars,
                      pl.BlockSpec((n, sb, 128),
                                   lambda i, j, counts: (0, j, 0)),
                      pl.BlockSpec((sb, 128), lambda i, j, counts: (j, 0)),
                      held],
            out_specs=[rows, held]),
        out_shape=[jax.ShapeDtypeStruct((m, ch, dt, 128), jnp.float32),
                   jax.ShapeDtypeStruct((m, n, dt, 128), jnp.float32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="selective_scan",
    )(counts.astype(jnp.int32), tiles(delta), tiles(x),
      b.reshape(m, 1, ch * n), c.reshape(m, 1, ch * n), tiles(a),
      skip.reshape(dt, 128), tiles(state))
    return y.reshape(m, ch, d), state.reshape(m, n, d)


# ---------------------------------------------------------------------------
# The gated delta rule (Qwen3-Next's linear attention; kind 6)
# ---------------------------------------------------------------------------
#
# ``H_v`` value heads of ``d_v`` over ``G`` key heads of ``d_k`` (key head
# ``g`` serves the ``R = H_v / G`` consecutive value heads ``g·R ..``), a
# state ``S [d_k, d_v]`` float32 a value head, from zero:
#
#     S ← e^{g_t}·S ;  r = Sᵀk_t ;  S ← S + k_t ⊗ β_t(v_t − r) ;  o_t = Sᵀq_t
#
# with ``q``, ``k`` unit vectors a head (``q`` times ``d_k^-1/2``), ``β_t =
# σ(b_t)`` and ``g_t = −exp(A_log)·softplus(a_t + dt_bias)`` a value head
# (:func:`delta_inputs`; a position past a row's ``counts`` has ``g = 0`` and
# ``β = 0``: it advances nothing). The state is read WITH ``k`` before it is
# written: a chunk is no masked matmul of the inputs alone. Both forms take
# ``sel = (q, k [m, c, G, d_k], v [m, c, G, R, d_v], β, g [m, c, G, R])``,
# float32, where the scans above take ``dt``.

#: the chunk form's triangular solve inverts ``I + A`` in diagonal blocks of
#: this many positions by forward substitution — ``DELTA_SUB_BLOCK − 1``
#: elementwise steps, every block, row and head at once — and joins pairs of
#: blocks upward (``[[P, 0], [C, R]]⁻¹ = [[P⁻¹, 0], [−R⁻¹CP⁻¹, R⁻¹]]``: two
#: batched matmuls a level, two levels at a chunk of 128). On the v5e, 8
#: rows of a 128-token chunk at Qwen3-Next's widths: 2.35 ms a layer at 16,
#: 2.03 at 32 (``tools/chip_check_qwen3_next.py --forms``; PERF.md §6, PR 62)
DELTA_SUB_BLOCK = 32

_HIGHEST = lax.Precision.HIGHEST
#: the precision of the chunk form's products that do NOT touch the carried
#: state — ``K·Kᵀ``, ``Q·Kᵀ``, the solve's joins, ``T·[βγK | βV]``,
#: ``tril(Q·Kᵀ)·V′``: float32 operands in full (PERF.md §6, PR 62 has the
#: readings beside bf16 passes)
DELTA_CHUNK_PRECISION = lax.Precision.HIGHEST


def delta_inputs(cfg, p, u: jax.Array, ba, counts: jax.Array):
    """What both forms take, from the convolved channels u [m, c, 2·G·d_k +
    H_v·d_v] (``[q | k | v]``, float32) and the token-wise ``ba = (b, a)``
    [m, c, H_v] each: ``q ← q / ‖q‖ / √d_k``, ``k ← k / ‖k‖`` a key head
    (``x·rsqrt(Σx² + 1e-6)``), ``β = σ(b)``, ``g = −exp(A_log) ⊙ softplus(a
    + dt_bias)``; β and g 0 past a row's ``counts``."""
    m, c = u.shape[:2]
    g_, n, h, hd = cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_heads, \
        cfg.ssm_head_dim
    u = u.astype(jnp.float32)

    def unit(x):
        x = x.reshape(m, c, g_, n)
        return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

    q = unit(u[..., :g_ * n]) * (n ** -0.5)
    k = unit(u[..., g_ * n:2 * g_ * n])
    v = u[..., 2 * g_ * n:].reshape(m, c, g_, h // g_, hd)
    b, a = (t.astype(jnp.float32) for t in ba)
    live = (jnp.arange(c, dtype=jnp.int32)[None] < counts[:, None])[..., None]
    beta = jnp.where(live, jax.nn.sigmoid(b), 0.0)
    g = jnp.where(live, -jnp.exp(p["A_log"].astype(jnp.float32)) *
                  jax.nn.softplus(a + p["dt_bias"].astype(jnp.float32)), 0.0)
    return q, k, v, beta.reshape(m, c, g_, -1), g.reshape(m, c, g_, -1)


def delta_step(cfg, p, u, sel, state: jax.Array, counts, reset=None):
    """:func:`scan_step`'s part for a gated delta rule: rows of ONE
    position, ``sel`` of :func:`delta_inputs`, state [m, H_v, d_k, d_v]
    float32 → (o [m, 1, H_v·d_v] float32, the state after it); ``reset``
    rides in the decay. Elementwise in float32: bound by the state's bytes,
    2 x 2 MiB a live row at Qwen3-Next's 32 heads of 128 x 128."""
    q, k, v, beta, g = (t[:, 0] for t in sel)
    m = q.shape[0]
    decay = jnp.exp(g)                                      # [m, G, R]
    if reset is not None:
        decay = jnp.where(reset[:, None, None], 0.0, decay)
    s = state.reshape((m,) + g.shape[1:] + state.shape[2:])  # [m,G,R,N,P]
    s = decay[..., None, None] * s
    k_col, q_col = k[:, :, None, :, None], q[:, :, None, :, None]
    delta = beta[..., None] * (v - jnp.sum(s * k_col, axis=-2))  # [m,G,R,P]
    s = s + k_col * delta[..., None, :]
    o = jnp.sum(s * q_col, axis=-2)
    return o.reshape(m, 1, cfg.ssm_inner), s.reshape(state.shape)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + A)⁻¹`` for ``a`` [..., c, c] STRICTLY lower triangular, ``c`` a
    power of two times ``DELTA_SUB_BLOCK``, float32. The diagonal blocks by forward
    substitution a row at a time (row ``i`` of the inverse's strict part is
    ``−a_i − Σ_{j<i} a_ij·row_j``: elementwise, exact float32), then pairs of
    inverted blocks joined upward with matmuls at ``Precision.HIGHEST``."""
    c, b = a.shape[-1], DELTA_SUB_BLOCK
    lead = a.shape[:-2]

    def diagonal_blocks(blocks):    # [.., n, s, n, s] → [.., n, s, s]
        return jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)

    def substitute(i, rows):
        """ONE loop body, not ``b − 1`` unrolled ones (half a step program's
        build at Qwen3-Next's nine layers); ``rows`` [b, .., b]: the row
        index LEADS, so a row's update is in place."""
        row = lax.dynamic_index_in_dim(rows, i, axis=0, keepdims=False)
        row = row + jnp.sum(jnp.moveaxis(row, -1, 0)[..., None] * rows,
                            axis=0)
        return lax.dynamic_update_index_in_dim(rows, row, i, axis=0)

    t = -diagonal_blocks(a.reshape(lead + (c // b, b, c // b, b)))
    t = jnp.moveaxis(lax.fori_loop(1, b, substitute,
                                   jnp.moveaxis(t, -2, 0)), 0, -2)
    t = t + jnp.eye(b, dtype=a.dtype)
    s = b
    while s < c:
        # [[P, 0], [C, R]]: C is the block at (odd, even) of each pair
        nb = c // s
        blocks = a.reshape(lead + (nb // 2, 2, s, nb // 2, 2, s))
        cross = diagonal_blocks(blocks[..., :, 1, :, :, 0, :])
        pairs = t.reshape(lead + (nb // 2, 2, s, s))
        p_inv, r_inv = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", r_inv, cross, p_inv,
                          precision=DELTA_CHUNK_PRECISION)
        top = jnp.concatenate([p_inv, jnp.zeros_like(p_inv)], axis=-1)
        t = jnp.concatenate(
            [top, jnp.concatenate([low, r_inv], axis=-1)], axis=-2)
        s *= 2
    return t.reshape(lead + (c, c))


def delta_chunk(cfg, p, u, sel, state: jax.Array, counts,
                kernel: bool = False):
    """:func:`scan_chunk`'s part for a gated delta rule (the WY form): rows
    of ``c`` positions from a CARRIED state [m, H_v, d_k, d_v] float32 → (o
    [m, c, H_v·d_v] float32, the state after each row's last live
    position). With ``γ_t = exp(Σ_{s≤t} g_s)`` inside the chunk: ``A =
    tril₋₁(β_t·γ_t/γ_s·k_t·k_s)``, ``T = (I + A)⁻¹``
    (:func:`_unit_lower_inverse`), ``W = T·(βγ ⊙ K)``, ``U = T·(β ⊙ V)``,
    ``V′ = U − W·S_in``, ``O = (γ ⊙ Q)·S_in + tril(γ_t/γ_s·Q·Kᵀ)·V′``,
    ``S_out = γ_end·S_in + (γ_end/γ ⊙ K)ᵀ·V′``. Every ratio of decays is
    ``exp`` of a difference masked BEFORE the exponential (no division by an
    underflowed ``γ``). ``K·Kᵀ`` and ``Q·Kᵀ`` are a KEY head's, scaled a
    value head. What reads or makes the carried state is float32 at
    ``Precision.HIGHEST`` (``scan_chunk`` says why); the products inside
    the chunk at ``DELTA_CHUNK_PRECISION`` (a chunk's products are 80 GFLOP
    a launch of seven chunk rows at Qwen3-Next's widths: PERF.md §5). A chunk whose
    width is no power of two times ``DELTA_SUB_BLOCK`` is padded with
    positions that advance nothing. ``kernel``: the same mathematics as ONE
    Pallas kernel (:func:`delta_chunk_kernel`: every ``[c, c]`` matrix, the
    solve and the carried state in VMEM, a row's dead positions not
    computed) where the heads are whole lane tiles and the chunk is whole
    turns of the kernel's (:func:`delta_kernel_takes`); else what follows,
    in XLA (the CPU's form, the rehearsal widths, the kernel's
    reference)."""
    q, k, v, beta, g = sel
    m, c = q.shape[:2]
    if kernel and delta_kernel_takes(q.shape[-1], v.shape[-1], c):
        # the values as they lie behind ``[q | k]`` in the convolved
        # channels where those are float32 and ``[q | k]`` is whole blocks
        # of a program's lanes (no copy of a slice), else alone
        alone = v.reshape(m, c, -1)
        lies = u is not None and u.dtype == jnp.float32 and \
            (u.shape[-1] - alone.shape[-1]) % (v.shape[3] * v.shape[4]) == 0
        return delta_chunk_kernel(
            q.reshape(m, c, -1), k.reshape(m, c, -1), u if lies else alone,
            beta.reshape(m, c, -1), g.reshape(m, c, -1), state, counts,
            sub=DELTA_KERNEL_SUB)
    blocks = 1
    while blocks * DELTA_SUB_BLOCK < c:
        blocks *= 2
    pad = blocks * DELTA_SUB_BLOCK - c
    if pad:
        q, k, v, beta, g = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (t.ndim - 2)) for t in (q, k, v, beta, g))
    s_in = state.reshape((m,) + g.shape[2:] + state.shape[2:])  # [m,G,R,N,P]
    cum = jnp.cumsum(g, axis=1)                             # [m, c, G, R]
    t = jnp.arange(c + pad, dtype=jnp.int32)
    seg = jnp.moveaxis(cum, 1, -1)                          # [m, G, R, c]
    seg = seg[..., :, None] - seg[..., None, :]             # [.., t, s]
    ratio = jnp.exp(jnp.where(t[:, None] >= t[None], seg, -jnp.inf))
    strict = (t[:, None] > t[None]).astype(jnp.float32)
    inner = DELTA_CHUNK_PRECISION
    kk = jnp.einsum("mtgn,msgn->mgts", k, k, precision=inner)
    qk = jnp.einsum("mtgn,msgn->mgts", q, k, precision=inner)
    beta_t = jnp.moveaxis(beta, 1, -1)[..., None]           # [m, G, R, c, 1]
    inv = _unit_lower_inverse(beta_t * ratio * strict * kk[:, :, None])
    gamma = jnp.exp(cum)                                    # [m, c, G, R]
    k_heads = k[:, :, :, None]                              # [m, c, G, 1, N]
    w = jnp.einsum("mgrts,msgrn->mtgrn", inv,
                   (beta * gamma)[..., None] * k_heads, precision=inner)
    uu = jnp.einsum("mgrts,msgrp->mtgrp", inv, beta[..., None] * v,
                    precision=inner)
    v_new = uu - jnp.einsum("mtgrn,mgrnp->mtgrp", w, s_in,
                            precision=_HIGHEST)
    o = jnp.einsum("mtgrn,mgrnp->mtgrp", gamma[..., None] * q[:, :, :, None],
                   s_in, precision=_HIGHEST) + \
        jnp.einsum("mgrts,msgrp->mtgrp", ratio * qk[:, :, None], v_new,
                   precision=inner)
    to_end = jnp.exp(cum[:, -1:] - cum)                     # [m, c, G, R]
    s_out = gamma[:, -1][..., None, None] * s_in + jnp.einsum(
        "mtgrn,mtgrp->mgrnp", to_end[..., None] * k_heads, v_new,
        precision=_HIGHEST)
    return o[:, :c].reshape(m, c, cfg.ssm_inner), s_out.reshape(state.shape)


#: positions a turn of :func:`delta_chunk_kernel`'s walk over a chunk row
#: takes: the row's state stays in VMEM between turns, and the walk ends
#: with the row's last live position. On the v5e, 8 rows of a 128-token
#: chunk at Qwen3-Next's widths, ms a layer-call at turns of 32 | 64 | 128:
#: 0.90 | 0.83 | 1.58 full, 0.85 | 0.79 | 1.49 filled as cell 14's launch
#: fills them; 64 rows 8.5 | 7.7 | 10.8 (``tools/chip_check_qwen3_next.py --forms``; PERF.md §6, PR 63)
DELTA_KERNEL_SUB = 64


def delta_kernel_takes(d_k: int, d_v: int, c: int) -> bool:
    """Rows of ``c`` positions at heads of ``d_k`` x ``d_v`` are the
    kernel's: whole lane tiles a head, whole turns a chunk."""
    return d_k % 128 == 0 and d_v % 128 == 0 and c % DELTA_KERNEL_SUB == 0


def _delta_chunk_body(counts_ref, q_ref, k_ref, v_ref, row_ref, col_ref,
                      s_ref, o_ref, so_ref, *, sub):
    """Grid (rows, key heads): ONE program carries the states ``[d_k,
    d_v]`` of a row's ``R`` value heads on one key head — they share
    ``K·Kᵀ`` and ``Q·Kᵀ`` — through the row's LIVE positions
    (``counts_ref``, scalar-prefetched), ``sub`` at a turn: a turn is
    :func:`delta_chunk`'s mathematics on ``sub`` positions from the state
    the turn before left in ``so_ref``, so a row of 23 live positions takes
    one turn and a row with none takes none (its state copied through, its
    outputs zero, as every position past a row's last turn). ``q_ref`` /
    ``k_ref`` ``[1, c, d_k]`` (the key head's lanes), ``v_ref`` / ``o_ref``
    ``[1, c, R·d_v]``; a turn's ``Σg`` from its first position on and
    ``β``, a head each, as ROWS (``row_ref`` ``[1, 1, c / sub, 2·R, sub]``)
    and as COLUMNS (``col_ref`` ``[1, 1, c, 2·R]``): a ``[sub, sub]``
    matrix of decay ratios is a column less a row; ``s_ref`` / ``so_ref``
    ``[1, R, d_k, d_v]``, aliased."""
    heads, d_k, d_v = s_ref.shape[1:]
    f32, inner = jnp.float32, DELTA_CHUNK_PRECISION
    live = counts_ref[pl.program_id(0)]
    o_ref[...] = jnp.zeros(o_ref.shape, f32)
    so_ref[...] = s_ref[...]
    t_i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    s_i = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    tile_v, tile_t, tile_s = (lax.broadcasted_iota(
        jnp.int32, (sub // 8, 8, sub), axis) for axis in range(3))

    def dot(x, y, contract, precision):
        return lax.dot_general(x, y, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=f32)

    nn, nt, tn = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))

    def inverse(a):
        """``(I + a)⁻¹`` a head, ``a`` [R, sub, sub] strictly lower, by
        forward substitution, exact float32: a finished row ``i`` leaves the
        rows under it (``row_j −= a_ji·row_i``: a lane of ``a`` times a row,
        no sum across a tile), in the tiles of 8 rows at and under ``i``
        alone — ``sub − 1`` steps on what stays in registers, where
        :func:`_unit_lower_inverse` takes 31 on arrays in HBM and then joins
        blocks with matmuls. The heads as ONE array: half the equations a
        trace of the body holds, the same vector operations."""
        tiles = sub // 8
        a = a.reshape(heads, tiles, 8, sub)
        x = jnp.broadcast_to((tile_v * 8 + tile_t == tile_s).astype(f32),
                             a.shape)
        for i in range(sub - 1):
            row = x[:, i // 8, i % 8:i % 8 + 1]              # [R, 1, sub]
            under = (i + 1) // 8
            left = x[:, under:] - a[:, under:, :, i:i + 1] * row[:, None]
            x = jnp.concatenate([x[:, :under], left], axis=1) if under \
                else left
        return x.reshape(heads, sub, sub)

    def turn(j):
        at = pl.multiple_of(j * sub, sub)
        kj, qj = k_ref[0, pl.ds(at, sub), :], q_ref[0, pl.ds(at, sub), :]
        both = dot(jnp.concatenate([kj, qj], axis=0), kj, nt, inner)
        kk, qk = both[:sub], both[sub:]
        rows, cols = row_ref[0, 0, j], col_ref[0, 0, pl.ds(at, sub), :]
        ratios, betas, cums = [], [], []
        for r in range(heads):
            cum_row = rows[2 * r:2 * r + 1]
            cum_col, beta_col = (cols[:, 2 * r + i:2 * r + i + 1]
                                 for i in range(2))
            # every ratio of decays: masked BEFORE the exponential
            ratios.append(jnp.exp(jnp.where(t_i >= s_i, cum_col - cum_row,
                                            -jnp.inf)))
            betas.append(beta_col)
            cums.append(cum_col)
        inv = inverse(jnp.stack([
            beta_col * jnp.where(t_i > s_i, ratio, 0.0) * kk
            for beta_col, ratio in zip(betas, ratios)]))
        for r, (ratio, beta_col, cum_col) in enumerate(
                zip(ratios, betas, cums)):
            gamma = jnp.exp(cum_col)
            vj = v_ref[0, pl.ds(at, sub), r * d_v:(r + 1) * d_v]
            wu = dot(inv[r], jnp.concatenate(
                [beta_col * gamma * kj, beta_col * vj], axis=1), nn, inner)
            s = so_ref[0, r]
            read = dot(jnp.concatenate([wu[:, :d_k], gamma * qj], axis=0), s,
                       nn, _HIGHEST)
            v_new = wu[:, d_k:] - read[:sub]
            o_ref[0, pl.ds(at, sub), r * d_v:(r + 1) * d_v] = \
                read[sub:] + dot(ratio * qk, v_new, nn, inner)
            cum_end = cum_col[sub - 1:sub]
            so_ref[0, r] = \
                jnp.exp(jnp.broadcast_to(cum_end, (1, d_v))) * s + dot(
                    jnp.exp(cum_end - cum_col) * kj, v_new, tn, _HIGHEST)

    lax.fori_loop(0, pl.cdiv(live, sub), lambda j, _: turn(j), None)


# jitted, as :func:`selective_scan_kernel` is: ONE trace of the body for a
# program's unrolled layers and instances of one shape
@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def delta_chunk_kernel(q, k, v, beta, g, state, counts, sub: int,
                       interpret: bool = False):
    """The gated delta rule's chunk form as a Pallas kernel
    (:func:`_delta_chunk_body`; its name in a device trace is
    ``delta_chunk``): q, k [m, c, G·d_k] (unit vectors a head, as
    :func:`delta_inputs` makes them), v [m, c, .. + H_v·d_v] — the value
    heads are its LAST lanes: the convolved channels as they lie, or the
    values alone —, beta, g [m, c, H_v] (0 past a row's ``counts``), state
    [m, H_v, d_k, d_v], all float32, counts [m] → (o [m, c, H_v·d_v], the
    state after each row's last live position). ``d_k`` and ``d_v`` are
    whole lane tiles and ``c`` whole turns of ``sub`` positions
    (``DELTA_KERNEL_SUB``). HBM traffic: q, k, v, o once each, the state in
    once and out once; nothing ``[c, c]``."""
    m, c, _ = q.shape
    h_v, d_k, d_v = state.shape[1:]
    groups = q.shape[-1] // d_k                     # G
    heads, turns = h_v // groups, c // sub          # R
    lead = (v.shape[-1] - h_v * d_v) // (heads * d_v)
    # [m, c, G, 2·R]: a turn's Σg from its first position on, and β
    vecs = jnp.stack([jnp.cumsum(g.reshape(m, turns, sub, h_v), axis=2)
                      .reshape(m, c, groups, heads),
                      beta.reshape(m, c, groups, heads)],
                     axis=-1).reshape(m, c, groups, 2 * heads)
    keys = pl.BlockSpec((1, c, d_k), lambda i, j, counts: (i, 0, j))
    held = pl.BlockSpec((1, heads, d_k, d_v),
                        lambda i, j, counts: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_delta_chunk_body, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(m, groups),
            in_specs=[keys, keys,
                      pl.BlockSpec((1, c, heads * d_v),
                                   lambda i, j, counts: (i, 0, lead + j)),
                      pl.BlockSpec((1, 1, turns, 2 * heads, sub),
                                   lambda i, j, counts: (i, j, 0, 0, 0)),
                      pl.BlockSpec((1, 1, c, 2 * heads),
                                   lambda i, j, counts: (i, j, 0, 0)),
                      held],
            out_specs=[pl.BlockSpec((1, c, heads * d_v),
                                    lambda i, j, counts: (i, 0, j)), held]),
        out_shape=[jax.ShapeDtypeStruct((m, c, h_v * d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="delta_chunk",
    )(counts.astype(jnp.int32), q, k, v,
      vecs.reshape(m, turns, sub, groups, 2 * heads).transpose(0, 3, 1, 4, 2),
      vecs.transpose(0, 2, 1, 3), state)
