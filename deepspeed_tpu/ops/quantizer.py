"""Block quantization kernels (int8 / int4 / fp8) — ZeRO++ & inference.

TPU-native replacement for the reference's CUDA quantizer family
(csrc/quantization/{quantize,dequantize,quant_reduce,quantize_intX}.cu,
csrc/fp_quantizer/) used by ZeRO++ qwZ/qgZ (runtime/zero/stage3.py:1636,
runtime/comm/coalesced_collectives.py) and inference weight quant.

Layout: a flat [n] tensor is viewed as [n/B, B] blocks; each block gets one
fp32 scale (symmetric absmax) or (scale, zero-point) pair (asymmetric
min/max). int4 packs two values per uint8 byte. All shapes static; the XLA
path is a fused reshape→reduce→round (one HBM pass); the Pallas kernel does
the same tile-resident for use inside larger fused kernels.

Error bound (symmetric int8): |x - dq(q(x))| ≤ absmax(block) / 254
per element — tested in tests/test_quantization.py.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 256


def _as_blocks(x: jax.Array, block: int) -> jax.Array:
    n = x.shape[0]
    if n % block:
        raise ValueError(f"length {n} not divisible by block {block} "
                         f"(pad upstream)")
    return x.reshape(n // block, block)


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------

def quantize_blocks(x: jax.Array, block: int = DEFAULT_BLOCK, bits: int = 8,
                    symmetric: bool = True
                    ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """flat f32/bf16 [n] → (q, scales [n/B] f32, zero_points or None).

    bits=8: q int8 in [-127, 127] (symmetric) or uint8 with zero-point.
    bits=4: q uint8 [n/2] — two nibbles per byte, values in [-7, 7] + 8.
    """
    xb = _as_blocks(x.astype(jnp.float32), block)
    qmax = 127.0 if bits == 8 else 7.0
    if symmetric:
        absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        scales = absmax / qmax
        safe = jnp.where(scales > 0, scales, 1.0)
        q = jnp.clip(jnp.round(xb / safe), -qmax, qmax)
        zp = None
    else:
        lo = jnp.min(xb, axis=1, keepdims=True)
        hi = jnp.max(xb, axis=1, keepdims=True)
        scales = (hi - lo) / (2 * qmax)
        safe = jnp.where(scales > 0, scales, 1.0)
        zp = lo
        q = jnp.clip(jnp.round((xb - lo) / safe) - qmax, -qmax, qmax)
    if bits == 8:
        packed = q.astype(jnp.int8).reshape(-1)
    elif bits == 4:
        u = (q + 8).astype(jnp.uint8).reshape(-1, 2)
        packed = (u[:, 0] | (u[:, 1] << 4)).astype(jnp.uint8)
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    return packed, scales[:, 0], (zp[:, 0] if zp is not None else None)


def dequantize_blocks(q: jax.Array, scales: jax.Array,
                      zero_points: Optional[jax.Array] = None,
                      block: int = DEFAULT_BLOCK, bits: int = 8,
                      dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_blocks` → flat [n] of ``dtype``."""
    if bits == 8:
        vals = q.astype(jnp.float32).reshape(-1, block)
    elif bits == 4:
        lo = (q & 0xF).astype(jnp.int32) - 8
        hi = (q >> 4).astype(jnp.int32) - 8
        vals = jnp.stack([lo, hi], axis=1).reshape(-1, block) \
            .astype(jnp.float32)
    else:
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    out = vals * scales[:, None]
    if zero_points is not None:
        qmax = 127.0 if bits == 8 else 7.0
        out = (vals + qmax) * scales[:, None] + zero_points[:, None]
    return out.reshape(-1).astype(dtype)


def fp8_cast(x: jax.Array, dtype=jnp.float8_e4m3fn) -> jax.Array:
    """FP8 weight cast (reference csrc/fp_quantizer FP6/FP8 path — on TPU
    fp8 is a native dtype; the 'kernel' is a convert XLA fuses)."""
    return x.astype(dtype)


_FP8_E4M3_MAX = 448.0


def quantize_fp8_blocks(x: jax.Array, block: int = DEFAULT_BLOCK):
    """Block-scaled fp8-e4m3 quantization (reference ops/fp_quantizer
    FP_Quantize with q_bits=8, mantissa_bits=3 — the 'FP6-LLM' family).

    Per-block absmax scaling stretches each block onto the ±448 e4m3
    range, so small-magnitude weight blocks keep their mantissa precision
    instead of flushing toward zero. Returns (q fp8 [n], scales fp32
    [n/block])."""
    xb = _as_blocks(x.astype(jnp.float32), block)
    absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.maximum(absmax / _FP8_E4M3_MAX, 1e-12)
    q = (xb / scale).astype(jnp.float8_e4m3fn).reshape(-1)
    return q, scale[:, 0]


def dequantize_fp8_blocks(q: jax.Array, scales: jax.Array,
                          block: int = DEFAULT_BLOCK,
                          dtype=jnp.float32) -> jax.Array:
    xb = _as_blocks(q, block).astype(jnp.float32) * scales[:, None]
    return xb.reshape(-1).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas fused kernel (int8 symmetric — the qwZ/qgZ hot path)
# ---------------------------------------------------------------------------

def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)                  # [rows, B]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -qmax, qmax).astype(jnp.int8)
    s_ref[...] = scale[:, 0]


def quantize_blocks_pallas(x: jax.Array, block: int = DEFAULT_BLOCK,
                           rows_per_program: int = 64,
                           interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """Fused symmetric-int8 block quantize (one VMEM-resident pass)."""
    xb = _as_blocks(x, block)
    nb = xb.shape[0]
    rp = min(rows_per_program, nb)
    while nb % rp:
        rp -= 1
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=127.0),
        name="quantize_blocks",
        grid=(nb // rp,),
        in_specs=[pl.BlockSpec((rp, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rp, block), lambda i: (i, 0)),
                   pl.BlockSpec((rp,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb,), jnp.float32)],
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s
