"""The Pallas kernels of the main paths compile for a described TPU v5e.

``JAX_PLATFORMS=cpu`` stays set: the TPU's compiler is installed and
compiles for a chip that is described, not attached (the measurement
guide's third rehearsal). Nothing runs — these tests say a kernel lowers
through Mosaic at the ``1b`` preset's shapes (what interpret mode cannot
say: tile alignment, VMEM limits), not that its numbers are right.

Rules this file keeps (a breach makes every xdist worker collect different
tests, and the whole suite then runs nothing):

- the topology is described inside the module-scoped ``topo`` fixture —
  never at import, in a ``skipif`` condition or in a ``parametrize``
  argument; the cases below are plain strings and shape-only builders;
- the compile happens in the test's own process (the worker that loaded the
  TPU library keeps it until it exits; a child could not load it);
- the persistent compile cache is off around the compiles (an entry written
  for a described chip cannot be read back without one, and warns);
- all such tests live in this one file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# -- the 1b preset's shapes (models/llama.py): 16 query / 8 KV heads of 128,
# hidden 2048, FFN 8192; training at batch 8 x sequence 2048

def _flash(seq, batch, grad):
    from deepspeed_tpu.ops.flash_attention import flash_attention
    q = ((batch, seq, 16, 128), jnp.bfloat16)
    kv = ((batch, seq, 8, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    return fn, (q, kv, kv), 3 if grad else 1


def _paged(n, c, with_lse):
    """Paged attention over the default serving arena: 16 layers of
    512 pages (+1 trash page each) of 128 tokens, 8 KV heads of 128."""
    from deepspeed_tpu.ops import paged_attention as pa
    kern = pa.paged_attention_with_lse if with_lse else pa.paged_attention
    arena = ((8, 16 * (512 + 1), 128, 128), jnp.bfloat16)
    q = ((n, c, 16, 128), jnp.bfloat16)
    pt = ((n, 8), jnp.int32)               # max_seq_len 1024 = 8 pages
    vec = ((n,), jnp.int32)
    return functools.partial(kern, interpret=False), \
        (q, arena, arena, pt, vec, vec), 1


def _dequant(mode):
    """Weight-only dequant matmul at the decode shape of the 1b FFN up
    projection: 16 rows x [2048, 8192]."""
    from deepspeed_tpu.ops.quantized_linear import qmatmul, quantize_weight
    wq, scale = jax.eval_shape(
        functools.partial(quantize_weight, mode=mode),
        jax.ShapeDtypeStruct((2048, 8192), jnp.float32))
    return functools.partial(qmatmul, interpret=False), \
        (((16, 2048), jnp.bfloat16), (wq.shape, wq.dtype),
         (scale.shape, scale.dtype)), 1


def _grouped_glu(grad):
    """The grouped-matmul GLU FFN at bench.py's 1b/8-expert MoE widths:
    hidden 1024, expert FFN 2816, 8 experts, top-2 of 8 x 2048 tokens."""
    from deepspeed_tpu.ops import grouped_matmul as gmm
    d, f, e, k, s = 1024, 2816, 8, 2, 8 * 2048
    bm, bnf, bnd = gmm.pick_blocks(d, f, 2)
    r_pad = -(-s * k // bm) * bm + e * bm

    def loss(xs, wg, wi, wo, w, group_of_tile, sizes, live):
        z = gmm.grouped_glu_ffn(xs, wg, wi, wo, group_of_tile, sizes, live,
                                bm=bm, bnf=bnf, bnd=bnd, w=w,
                                interpret=False)
        return jnp.sum(z.astype(jnp.float32))

    bf = jnp.bfloat16
    args = (((r_pad, d), bf), ((e, d, f), bf), ((e, d, f), bf),
            ((e, f, d), bf), ((r_pad,), bf), ((r_pad // bm,), jnp.int32),
            ((e,), jnp.int32), ((1,), jnp.int32))
    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if grad else loss
    return fn, args, 3 if grad else 2


#: case id -> builder of (fn, [(shape, dtype), ...], least number of Mosaic
#: custom calls the compiled program must carry). Shapes only: nothing here
#: touches a device or the topology.
CASES = {
    "flash_fwd_2k": lambda: _flash(2048, 8, grad=False),
    "flash_fwd_bwd_2k": lambda: _flash(2048, 8, grad=True),
    "flash_fwd_bwd_16k_1024_blocks": lambda: _flash(16384, 1, grad=True),
    "paged_decode_n16": lambda: _paged(16, 1, with_lse=False),
    "paged_decode_n16_lse": lambda: _paged(16, 1, with_lse=True),
    "paged_prefill_n4_c256": lambda: _paged(4, 256, with_lse=False),
    "dequant_int8": lambda: _dequant("int8"),
    "dequant_fp8": lambda: _dequant("fp8"),
    "dequant_int4": lambda: _dequant("int4"),
    "dequant_fp6": lambda: _dequant("fp6"),
    "grouped_glu_ffn_fwd": lambda: _grouped_glu(grad=False),
    "grouped_glu_ffn_fwd_bwd": lambda: _grouped_glu(grad=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes, min_calls = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls >= min_calls, (
        f"{case}: {calls} Mosaic custom call(s) in the compiled program, "
        f"expected at least {min_calls} — a reference path took the "
        f"kernel's place")
    # one v5e chip holds 16 GiB; a kernel alone must sit far inside it
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
             mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 2**30
