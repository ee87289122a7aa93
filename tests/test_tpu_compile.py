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
import re

import jax
import jax.numpy as jnp
import numpy as np
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


@pytest.fixture(autouse=True)
def no_ambient_mesh(monkeypatch):
    """Every program here is compiled for ONE described chip. A global
    mesh that another test file left on this xdist worker (eight CPU
    devices) would send the trainer's attention through
    ``flash_attention_sharded`` over it, and the lowering then refuses
    the mix of devices: the whole run failed so once in two (PRs 30, 31),
    by which files shared the worker."""
    from deepspeed_tpu.parallel import mesh
    monkeypatch.setattr(mesh, "_CURRENT_MESH", None)


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

def _flash(seq, batch, grad, heads=16, kv_heads=8, window=None):
    from deepspeed_tpu.ops.flash_attention import flash_attention
    q = ((batch, seq, heads, 128), jnp.bfloat16)
    kv = ((batch, seq, kv_heads, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    return fn, (q, kv, kv), 3 if grad else 1


def _paged(n, c, with_lse, heads=16, pages=8, window=None, kv_heads=8):
    """Paged attention over the default serving arena: 16 layers of
    512 pages (+1 trash page each) of 128 tokens, ``kv_heads`` (8) KV heads
    of 128 side by side on the lanes; ``pages`` a row (8: ``max_seq_len``
    1024). The split step's history reader (``with_lse``) takes each row's
    live-query count as the engine passes it."""
    from deepspeed_tpu.ops import paged_attention as pa
    arena = ((16 * (512 + 1), 128, kv_heads * 128), jnp.bfloat16)
    q = ((n, c, heads, 128), jnp.bfloat16)
    pt = ((n, pages), jnp.int32)
    vec = ((n,), jnp.int32)
    if not with_lse:
        return functools.partial(pa.paged_attention, interpret=False), \
            (q, arena, arena, pt, vec, vec), 1

    def hist(q, ak, av, pt, starts, counts, qcounts):
        return pa.paged_attention_with_lse(
            q, ak, av, pt, starts, counts, window=window, qcounts=qcounts)
    return hist, (q, arena, arena, pt, vec, vec, vec), 1


def _paged_wide(n, c):
    """The split step's history reader at Qwen3-Next's full-attention
    layers: 16 query heads on 2 KV heads of 256 (8 queries a KV head, two
    whole lane tiles a head; K and V pools 512 lanes a token, UNPADDED), 3
    layers of the cell's 5,504 pages, 86 a row; ``n`` rows of chunk ``c``
    (8 x 128: the 1,024-slot instance's chunk group, a block of 1,024 query
    rows; 64 x 128: the row form; 64 x 1: every row as one query)."""
    from deepspeed_tpu.ops.paged_attention import paged_attention_with_lse

    def fn(q, ak, av, pt, starts, qcounts):
        return paged_attention_with_lse(
            q, ak, av, pt, starts, jnp.zeros_like(starts),
            scale=256 ** -0.5, qcounts=qcounts)
    bf, pool = jnp.bfloat16, (3 * 5505, 128, 2 * 256)
    return fn, (((n, c, 16, 256), bf), (pool, bf), (pool, bf),
                ((n, 86), jnp.int32), ((n,), jnp.int32),
                ((n,), jnp.int32)), 1


def _paged_typed(kvh, n=64, c=128):
    """The split step's history reader over a TYPED arena at MiMo-V2.5's
    widths: 64 query heads, K heads of 192 padded to 256 lanes, V heads of
    128, pools ``[blocks, 128, kvh * width]`` (5 window layers
    of 8 KV heads with a window of 128; 2 full layers of 4), ``n`` rows of
    chunk ``c`` (64 x 128: the row form; 64 x 1 and 4 x 128: a grouped
    step's two calls), ``max_seq_len`` 1024."""
    from deepspeed_tpu.ops.paged_attention import paged_attention_with_lse
    layers, window = (5, 128) if kvh == 8 else (2, None)
    blocks = layers * 513

    def fn(q, ak, av, pt, starts, qcounts):
        return paged_attention_with_lse(
            q, ak, av, pt, starts, jnp.zeros_like(starts), window=window,
            scale=192 ** -0.5, qcounts=qcounts)
    bf = jnp.bfloat16
    return fn, (((n, c, 64, 256), bf), ((blocks, 128, kvh * 256), bf),
                ((blocks, 128, kvh * 128), bf), ((n, 8), jnp.int32),
                ((n,), jnp.int32), ((n,), jnp.int32)), 1


def _selective_scan(m, c=128, d=5120, n=16):
    """The selective scan's chunk form at Jamba2-3B's widths: ``m`` rows of
    a ``c``-token chunk, 5,120 channels of 16 states, float32 (the chunk
    group of a grouped split step: 4 or 8 rows; the row form: 64)."""
    from deepspeed_tpu.ops import ssm
    f = jnp.float32
    return ssm.selective_scan_kernel, (
        ((n, d), f), ((d,), f), ((m, c, d), f), ((m, c, d), f),
        ((m, c, n), f), ((m, c, n), f), ((m, n, d), f), ((m,), jnp.int32)), 1


def _delta_chunk(m, c=128, g=16, r=2, d=128):
    """The gated delta rule's chunk form at Qwen3-Next's widths: ``m`` rows
    of a ``c``-token chunk, 16 key heads of 128 serving 32 value heads of
    128, float32, the values read behind ``[q | k]`` in the convolved
    channels (a grouped split step's chunk group: 4 or 8 rows; the row
    form: 64)."""
    from deepspeed_tpu.ops import ssm
    f = jnp.float32
    return functools.partial(
        ssm.delta_chunk_kernel, sub=ssm.DELTA_KERNEL_SUB), (
        ((m, c, g * d), f), ((m, c, g * d), f), ((m, c, (2 + r) * g * d), f),
        ((m, c, g * r), f), ((m, c, g * r), f), ((m, g * r, d, d), f),
        ((m,), jnp.int32)), 1


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
    # the training cells' call (Mistral 7B: 4 sequences of 4,096 = the
    # window, 32 / 8 heads of 128: 128 x 4096 x 128 a kernel, GQA 4), a
    # window SHORTER than the sequence (window-side edge tiles: three
    # ranges a block), and a split step's own-chunk attention (64 rows of
    # one 128 x 128 tile)
    "flash_fwd_bwd_cell1_4k_window4k": lambda: _flash(
        4096, 4, grad=True, heads=32, kv_heads=8, window=4096),
    "flash_fwd_bwd_4k_window1k": lambda: _flash(
        4096, 4, grad=True, heads=32, kv_heads=8, window=1024),
    "flash_fwd_own_chunk_128": lambda: _flash(
        128, 64, grad=False, heads=32, kv_heads=8),
    "paged_decode_n16": lambda: _paged(16, 1, with_lse=False),
    "paged_decode_n16_lse": lambda: _paged(16, 1, with_lse=True),
    "paged_prefill_n4_c256": lambda: _paged(4, 256, with_lse=False),
    # the split step's history reader at the serving cell's shapes:
    # Mistral's 32 / 8 heads, 64 rows of chunk 128, max_seq_len 4096
    "paged_hist_n64_c128_lse": lambda: _paged(64, 128, with_lse=True,
                                              heads=32, pages=32),
    # ... the two calls of its grouped split steps (every row as one query;
    # the chunk group of the 512- and the 1,024-slot rung), the long-prompt
    # cell's 8-row program, and its decode program's reader
    "paged_hist_n64_c1_lse": lambda: _paged(64, 1, with_lse=True, heads=32,
                                            pages=32),
    "paged_hist_n4_c128_lse": lambda: _paged(4, 128, with_lse=True,
                                             heads=32, pages=32),
    "paged_hist_n8_c128_lse": lambda: _paged(8, 128, with_lse=True,
                                             heads=32, pages=32),
    "paged_decode_n64_h32": lambda: _paged(64, 1, with_lse=False, heads=32,
                                           pages=32),
    # ... and over a typed arena (MiMo-V2.5: unequal K / V widths, a
    # window), both layer kinds
    "paged_hist_typed_window_kv8_lse": lambda: _paged_typed(8),
    "paged_hist_typed_full_kv4_lse": lambda: _paged_typed(4),
    "paged_hist_typed_window_kv8_c1_lse": lambda: _paged_typed(8, 64, 1),
    "paged_hist_typed_full_kv4_c1_lse": lambda: _paged_typed(4, 64, 1),
    "paged_hist_typed_window_kv8_n4_lse": lambda: _paged_typed(8, 4, 128),
    "paged_hist_typed_full_kv4_n4_lse": lambda: _paged_typed(4, 4, 128),
    # ... and at Command A+'s: 16 rows of chunk 128, 16 queries a KV head
    # (a block of 2,048 query rows), a window of 4,096, 86 pages a row
    "paged_hist_n16_c128_q16_window_lse": lambda: _paged(
        16, 128, with_lse=True, heads=128, pages=86, window=4096),
    # ... and what its 16-row program's grouped instances call in that
    # one's place (PR 50): the chunk group of the 512- and the 1,024-slot
    # instance, and every row as a row of one query
    "paged_hist_n4_c128_q16_window_lse": lambda: _paged(
        4, 128, with_lse=True, heads=128, pages=86, window=4096),
    "paged_hist_n8_c128_q16_window_lse": lambda: _paged(
        8, 128, with_lse=True, heads=128, pages=86, window=4096),
    "paged_hist_n16_c1_q16_window_lse": lambda: _paged(
        16, 1, with_lse=True, heads=128, pages=86, window=4096),
    # ... and at Jamba2-3B's: 20 queries over ONE KV head (a block of 2,560
    # query rows, between Command A+'s 2,048 and the 4,096 that does not
    # fit; 20 is no multiple of 8 or 16: the small tile is 4 queries, 80
    # rows), 86 pages a row; the chunk group of the 1,024-slot instance,
    # the row form, and every row as one query
    "paged_hist_n8_c128_q20_mqa_lse": lambda: _paged(
        8, 128, with_lse=True, heads=20, pages=86, kv_heads=1),
    "paged_hist_n64_c128_q20_mqa_lse": lambda: _paged(
        64, 128, with_lse=True, heads=20, pages=86, kv_heads=1),
    "paged_hist_n64_c1_q20_mqa_lse": lambda: _paged(
        64, 1, with_lse=True, heads=20, pages=86, kv_heads=1),
    # ... and at Qwen3-Next's: heads of 256, two lane tiles a head
    "paged_hist_n8_c128_d256_lse": lambda: _paged_wide(8, 128),
    "paged_hist_n64_c128_d256_lse": lambda: _paged_wide(64, 128),
    "paged_hist_n64_c1_d256_lse": lambda: _paged_wide(64, 1),
    # the selective scan's chunk form at its two chunk groups and the row form
    "selective_scan_n4_c128": lambda: _selective_scan(4),
    "selective_scan_n8_c128": lambda: _selective_scan(8),
    "selective_scan_n64_c128": lambda: _selective_scan(64),
    # the gated delta rule's chunk form likewise
    "delta_chunk_n4_c128": lambda: _delta_chunk(4),
    "delta_chunk_n8_c128": lambda: _delta_chunk(8),
    "delta_chunk_n64_c128": lambda: _delta_chunk(64),
    "dequant_int8": lambda: _dequant("int8"),
    "dequant_fp8": lambda: _dequant("fp8"),
    "dequant_int4": lambda: _dequant("int4"),
    "dequant_fp6": lambda: _dequant("fp6"),
    "grouped_glu_ffn_fwd": lambda: _grouped_glu(grad=False),
    "grouped_glu_ffn_fwd_bwd": lambda: _grouped_glu(grad=True),
}

#: case id -> the kernels' ``name=``s, which must be the names of the
#: compiled program's custom-call INSTRUCTIONS: a device trace shows an
#: operation under its instruction's name, and the benchmark's readers find
#: a kernel by it (flash and paged attention since PR 25, the rest PR 28).
#: Differentiated bare, as here, a kernel's name is wrapped in its
#: transforms (``transpose_jvp_flash_bwd_dq__``); inside the trainer's
#: scopes it is not
KERNEL_NAMES = {
    "flash_fwd_2k": ("flash_fwd",),
    "flash_fwd_bwd_2k": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "flash_fwd_bwd_cell1_4k_window4k": ("flash_fwd", "flash_bwd_dq",
                                        "flash_bwd_dkv"),
    "flash_fwd_bwd_4k_window1k": ("flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv"),
    "flash_fwd_own_chunk_128": ("flash_fwd",),
    "paged_decode_n64_h32": ("paged_attn",),
    "paged_hist_n64_c128_lse": ("paged_attn_lse",),
    "paged_hist_n64_c1_lse": ("paged_attn_lse",),
    "paged_hist_n4_c128_lse": ("paged_attn_lse",),
    "paged_hist_n8_c128_lse": ("paged_attn_lse",),
    "paged_hist_typed_window_kv8_c1_lse": ("paged_attn_lse",),
    "paged_hist_typed_full_kv4_c1_lse": ("paged_attn_lse",),
    "paged_hist_typed_window_kv8_n4_lse": ("paged_attn_lse",),
    "paged_hist_typed_full_kv4_n4_lse": ("paged_attn_lse",),
    "paged_hist_typed_window_kv8_lse": ("paged_attn_lse",),
    "paged_hist_typed_full_kv4_lse": ("paged_attn_lse",),
    "paged_hist_n16_c128_q16_window_lse": ("paged_attn_lse",),
    "paged_hist_n4_c128_q16_window_lse": ("paged_attn_lse",),
    "paged_hist_n8_c128_q16_window_lse": ("paged_attn_lse",),
    "paged_hist_n16_c1_q16_window_lse": ("paged_attn_lse",),
    "paged_hist_n8_c128_q20_mqa_lse": ("paged_attn_lse",),
    "paged_hist_n64_c128_q20_mqa_lse": ("paged_attn_lse",),
    "paged_hist_n64_c1_q20_mqa_lse": ("paged_attn_lse",),
    "paged_hist_n8_c128_d256_lse": ("paged_attn_lse",),
    "paged_hist_n64_c128_d256_lse": ("paged_attn_lse",),
    "paged_hist_n64_c1_d256_lse": ("paged_attn_lse",),
    "selective_scan_n4_c128": ("selective_scan",),
    "selective_scan_n8_c128": ("selective_scan",),
    "selective_scan_n64_c128": ("selective_scan",),
    "delta_chunk_n4_c128": ("delta_chunk",),
    "delta_chunk_n8_c128": ("delta_chunk",),
    "delta_chunk_n64_c128": ("delta_chunk",),
    "dequant_int8": ("qmm",),
    "dequant_fp8": ("qmm",),
    "dequant_int4": ("qmm_int4",),
    "dequant_fp6": ("qmm_fp6",),
    "grouped_glu_ffn_fwd": ("gmm_gate_up", "gmm_down_w"),
    "grouped_glu_ffn_fwd_bwd": ("gmm_dgdu_rc", "gmm_dxs", "gmm_dw_pair"),
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
    for name in KERNEL_NAMES.get(case, ()):
        assert re.search(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(", text), (
            f"{case}: no custom-call instruction named {name!r}")


def _kernel_shape(jaxpr, found):
    """{kernel name: (loops, vector equations)} of the Pallas calls in a
    jaxpr: the ``while`` / ``scan`` equations of a kernel's body, and its
    equations with a non-scalar result, after dead code is dropped (the
    scalar range arithmetic is the scalar core's; the vector count is what
    the body costs)."""
    from jax._src.interpreters import partial_eval as pe
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _kernel_shape(sub, found)
            continue
        body = eqn.params["jaxpr"]
        body, _ = pe.dce_jaxpr(body, [True] * len(body.outvars))

        def walk(jp, acc):
            for e in jp.eqns:
                acc[0] += e.primitive.name in ("while", "scan")
                acc[1] += any(getattr(v.aval, "shape", ()) for v in e.outvars)
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub, acc)
            return acc
        found[eqn.params["name"]] = tuple(walk(body, [0, 0]))
    return found


#: case id -> {kernel: (loops, vector equations)}. A call of ONE tile a head
#: (the own-chunk attention every serving cell's split programs hold) is its
#: masked tile body once: no loop at all, and the 52 vector equations of
#: the parent's body (PR 58, measured on its tree by this function: (1,
#: 53), the one ``while`` equation and the same 52 in it). The training
#: cells' kernels are
#: ONE loop (the interior tiles) and the diagonal tile straight-line; a
#: window shorter than the sequence adds the window-side edge loop.
KERNEL_SHAPES = {
    "flash_fwd_own_chunk_128": {"flash_fwd": (0, 52)},
    "flash_fwd_bwd_cell1_4k_window4k": {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1},
    "flash_fwd_bwd_4k_window1k": {
        "flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2},
    "flash_fwd_bwd_2k": {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1},
}


@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_flash_kernel_emits_only_the_classes_that_occur(case):
    """Tile counts are static, so a class no block of a call holds costs
    its kernel no code (shapes only: nothing compiles)."""
    fn, shapes, _ = CASES[case]()
    found = _kernel_shape(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes)).jaxpr, {})
    for kernel, want in KERNEL_SHAPES[case].items():
        got = found[kernel]
        assert (got if isinstance(want, tuple) else got[0]) == want, (
            f"{case}: {kernel} holds {got[0]} loop(s) and {got[1]} vector "
            f"equations, expected {want}")


#: paged case id -> the KV heads a program of its kernel holds
#: (``paged_attention.heads_per_program`` of the case's shapes): every head
#: of a row where the block is a decode row's, one where it is a chunk's
PAGED_HEADS = {
    "paged_decode_n16": 8, "paged_decode_n64_h32": 8,
    "paged_hist_n64_c1_lse": 8,
    "paged_hist_n4_c128_lse": 1, "paged_hist_n64_c128_lse": 1,
    "paged_hist_typed_window_kv8_c1_lse": 8,
    "paged_hist_typed_full_kv4_c1_lse": 4,
    "paged_hist_typed_window_kv8_lse": 1,
    "paged_hist_n16_c128_q16_window_lse": 1,
    "paged_hist_n4_c128_q16_window_lse": 1,
    "paged_hist_n16_c1_q16_window_lse": 8,
    "paged_hist_n8_c128_q20_mqa_lse": 1, "paged_hist_n64_c1_q20_mqa_lse": 1,
}


@pytest.mark.parametrize("case", sorted(PAGED_HEADS))
def test_paged_kernel_copies_a_page_whole_where_it_holds_every_head(case):
    """The page copies of the traced kernel (the ``dma_start``s of its
    jaxpr: shapes only, nothing compiles): where a program holds every KV
    head of its row the source of each is a page as it lies, ``[page, :,
    :]`` — no lane slice, one contiguous copy a pool —, and where it holds
    fewer, the lanes of ITS heads, ``hp`` times a head's width."""
    fn, shapes, _ = CASES[case]()
    text = str(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes)))
    copies = re.findall(r"dma_start\(\w+\) \w+\[(\w+,:,[^\]]+)\] ->", text)
    assert len(copies) >= 4, text[:2000]      # first page + prefetch, K + V
    kvh = shapes[1][0][2] // shapes[0][0][3]
    hp = PAGED_HEADS[case]
    if hp == kvh:
        assert all(c.endswith(",:,:") for c in copies), copies
        return
    widths = {int(w) for c in copies
              for w in re.findall(r":\w+\+(\d+)$", c)}
    assert widths == {hp * shapes[pool][0][2] // kvh for pool in (1, 2)}, \
        copies


def test_history_kernel_at_chunk_256_and_16_queries_a_kv_head(
        one_chip, no_persistent_cache):
    """RECORDS whether ``paged_attn_lse`` compiles at a chunk of 256 with
    16 queries a KV head (Command A+'s block at ``prefill_chunk`` 256: a
    query block of 4,096 rows; PERF.md §7 (9)). It did not while every row
    computed its whole block, and it does not now: a row of more live
    queries than the small tile still walks its pages with the whole block
    (``paged_attention._paged_kernel``), whose scores and accumulator do
    not fit VMEM beside the q / out / lse blocks. An ``xfail`` with the
    compiler's words, not a failure: no configuration asks for this
    shape."""
    fn, shapes, _ = _paged(16, 256, with_lse=True, heads=128, pages=86,
                           window=4096)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:                               # noqa: BLE001
        assert "vmem" in str(e) and "paged_attn_lse" in str(e), e
        pytest.xfail(str(e).split("\n")[0][:300])


def test_quantizer_kernel_is_named():
    """``ops/quantizer.py``'s block quantizer carries its ``name=`` too. Not
    among the compiles above: Mosaic refuses the kernel's rank-1 scale
    output on a v5e (PERF.md, open questions), so the name is read from
    the traced call, which needs no chip."""
    from deepspeed_tpu.ops.quantizer import quantize_blocks_pallas
    jaxpr = jax.make_jaxpr(functools.partial(
        quantize_blocks_pallas, block=256, interpret=True))(
        jax.ShapeDtypeStruct((64 * 256,), jnp.bfloat16))
    assert "quantize_blocks" in str(jaxpr)


# -- whole step programs at Mistral-7B widths, two layers: the scope table
# (telemetry/explain.scope_table_from_hlo) names what the device runs

def _mistral_2l():
    import dataclasses
    from deepspeed_tpu.models.mistral import mistral_config
    return dataclasses.replace(mistral_config("7b"), num_layers=2)


def _abstract_params(model, sharding):
    from deepspeed_tpu.models.transformer import init_params
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=sharding),
        jax.eval_shape(lambda r: init_params(model, r),
                       jax.random.PRNGKey(0)))


#: the serving cell's three 64-row step programs -> (chunk width,
#: ``fresh_prefill``, token capacities under the engine's defaults
#: (``_token_capacities``: the split program's ladder of 8 and 16 a row
#: under ``max_batch_tokens``, ISSUE 46; a decode step keeps the row form),
#: most temporaries at two layers (measured 0.00, 0.46 and 0.11 GB since
#: the chunk's K/V leave the layer loop packed, ISSUE 36; 0.56 and 0.14
#: while they left it as rows))
_SERVE_STEPS = {
    "decode": (1, False, (), 0.3e9),
    "split": (128, "split", (512, 1024, 2048), 0.5e9),
    "fresh": (128, "fresh", (2048,), 0.125e9),
}
#: the split program's capacities before the rung at 8 a row (PRs 32-45):
#: what the three-rung program's memory is held against
_TWO_RUNGS = (1024, 2048)


#: stack -> temporaries of its 64-row split program at two layers before
#: PR 40 (bytes; this compiler, the tree at PR 39). The TWO-RUNG program's
#: instance at 1,024 slots runs attention on two row groups — 8 rows at the
#: chunk's width and 64 rows of one query — and must not grow them:
#: measured +0.5, +0.6 and +1.1 MB (the groups' index arrays); since PR 57
#: the held experts' many-token dispatch keeps its ``[slots, k, H]`` pick
#: mask (a byte an element, H padded to 128 lanes: 2 MB at 2,048 slots x 8
#: picks) where the sorted form kept ``[slots·k]`` keys: +2.3 and +2.7 MB
#: in the two stacks with experts, so 4 MB of room
_SPLIT_TEMPS_PR39 = {"uniform": 457330176, "mimo": 1046920704,
                     "latent": 1631744000}


def _memory(compiled):
    """``arguments + temporaries`` of a compiled program, bytes."""
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def _check_ladder_memory(compiled, two_rungs):
    """The three-rung split program against the two-rung one it replaced,
    the same stack compiled for the same chip: ``arguments + temporaries``
    within 0.1 GB (an instance more of the layer loop adds index arrays and
    no tensor of its own: the branches' temporaries share their space), and
    under the chip's 15.75 GiB."""
    assert _memory(compiled) <= _memory(two_rungs) + 0.1e9, \
        (_memory(compiled), _memory(two_rungs))
    assert _memory(compiled) < 15.75 * 2 ** 30


def _check_three_rungs(compiled, text, kernel, layer_loops, replaced):
    """A split program of THREE instances of its layer loop in one
    executable (ISSUE 46): three branches, the history ``kernel`` called
    five times a layer loop's layer (every row at the chunk's width in the
    top instance; the chunk group — 8 rows at 1,024 slots, 4 at 512 — and
    the one-query rows in each of the two under it), memory within 0.1 GB
    of the program it ``replaced``."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    assert len(_branches(text)) == 3, _branches(text)
    table = scope_table_from_hlo(text)
    kernels = [n for n in table if n.startswith(kernel)]
    assert len(kernels) == 5 * layer_loops and \
        all(table[n]["scope"] == "attn_history" for n in kernels), kernels
    _check_ladder_memory(compiled, replaced)


def _check_split_groups(stack, compiled, text, kernel, layer_loops,
                        two_rungs):
    """The 64-row split program of ``stack``: the three-rung ladder
    (:func:`_check_three_rungs`) beside the two-rung program ``two_rungs``
    — whose temporaries are no larger than before the groups."""
    _check_three_rungs(compiled, text, kernel, layer_loops, two_rungs)
    temp = two_rungs.memory_analysis().temp_size_in_bytes
    assert temp <= _SPLIT_TEMPS_PR39[stack] + 4e6, temp


def _serve_step(one_chip, kind="split", capacities=None):
    """A 64-row step of the benchmark's serving cell (``serve_decode_r64``,
    ``serve_split_r64_c128``, ``serve_fresh_r64_c128``): chunk 128 or one
    token a row over the default arena (512 pages of 128, ``max_seq_len``
    4096), a chunk's token-wise sublayers over the packed tokens at the
    cell's capacities."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.ops import paged_attention as pa
    model = _mistral_2l()
    cb, fresh, ladder, _ = _SERVE_STEPS[kind]
    capacities = ladder if capacities is None else capacities
    nb, mb = 64, 32

    def serve_step(params, arena, tokens, counts, starts, pt):
        logits, arena = engine_v2.ragged_forward(
            model, params, arena, tokens, counts, starts, pt,
            use_pallas=True, fresh_prefill=fresh,
            token_capacities=capacities)
        out, _ = engine_v2._sample_tokens(logits, ("argmax",), 1.0, 1.0,
                                          None)
        return out, arena

    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: pa.init_arena(
            model.num_layers, model.kv_heads, 512, 128, model.head_dim,
            jnp.bfloat16)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    return jax.jit(serve_step, donate_argnums=(1,)), (
        _abstract_params(model, one_chip), arena, i32(nb, cb), i32(nb),
        i32(nb), i32(nb, mb))


def _fused_step(one_chip):
    """The trainer's fused step as the benchmark's one-chip training cell
    configures it (bf16, AdamW with bf16 moments, clipping, remat
    ``save_attn_kernel``, bf16 chunked CE), at batch 2 x sequence 4096:
    the engine's own ``_compute_loss_and_grads`` and ``_apply_update`` on a
    stand-in for ``self`` that holds what they read (an engine cannot be
    built on a described device: it places real parameters)."""
    import types
    from deepspeed_tpu.config import DeepSpeedTPUConfig
    from deepspeed_tpu.ops.optimizers import build_optimizer
    from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine
    from deepspeed_tpu.runtime.loss_scaler import LossScaleState
    from deepspeed_tpu.runtime.lr_schedules import build_schedule
    from deepspeed_tpu.runtime.model_factory import decoder_model_spec
    cfg = DeepSpeedTPUConfig.from_any({
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {
            "lr": 1e-4, "weight_decay": 0.1, "state_dtype": "bfloat16",
            "master_weights": False}},
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "activation_checkpointing": {"policy": "save_attn_kernel"},
        "ce_logits_dtype": "bf16", "chunked_ce_budget_mb": 256,
        "attention_impl": "auto"})
    model = _mistral_2l()
    spec = decoder_model_spec(model, cfg)
    params = _abstract_params(model, one_chip)
    optimizer, base_lr = build_optimizer(cfg.optimizer.type,
                                         cfg.optimizer.params)
    on_chip = jax.tree.map(lambda _a: one_chip, params)
    stand_in = types.SimpleNamespace(
        model=spec, config=cfg, fp16_enabled=False, optimizer=optimizer,
        lr_schedule=build_schedule(cfg.scheduler.type, cfg.scheduler.params,
                                   base_lr),
        plan=types.SimpleNamespace(grad_shardings=lambda: on_chip),
        _param_shardings=on_chip, _health_enabled=False)

    def fused_step(params, opt_state, scaler, batch, step, rng):
        loss, fwd, grads = DeepSpeedTPUEngine._compute_loss_and_grads(
            stand_in, params, batch, scaler.scale, rng)
        return DeepSpeedTPUEngine._apply_update(
            stand_in, params, opt_state, scaler, grads, step, 1,
            fwd_metrics=fwd), loss

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    scaler = LossScaleState(jnp.float32(1.0), jnp.zeros((), jnp.int32),
                            jnp.zeros((), jnp.int32))
    return jax.jit(fused_step, donate_argnums=(0, 1)), (
        params, placed(jax.eval_shape(optimizer.init, params)),
        placed(jax.eval_shape(lambda: scaler)),
        {"input_ids": jax.ShapeDtypeStruct((2, 4096), jnp.int32,
                                           sharding=one_chip)},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        placed(jax.eval_shape(lambda: jax.random.PRNGKey(0))))


#: program -> (builder, scopes that must each name some instruction)
PROGRAMS = {
    "serve_split": (_serve_step, (
        "embed", "norm", "attn_qkv", "attn_core", "attn_history",
        "attn_merge", "kv_write", "attn_out", "mlp", "lm_head", "sample")),
    "fused_step": (_fused_step, (
        "embed", "norm", "attn_qkv", "attn_core", "attn_out", "mlp", "loss",
        "grad_clip", "optimizer")),
}
_HEAVY = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?\s(fusion|convolution|"
    r"custom-call)\(", re.M)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_step_program_maps_to_scopes_on_v5e(program, one_chip,
                                            no_persistent_cache,
                                            monkeypatch):
    """At least 90% of the fusions, convolutions and custom-calls of a step
    program compiled for the v5e map to a word of the scope vocabulary, the
    backward pass and the recomputed forward are told apart, and every
    layer part's scope names something."""
    from deepspeed_tpu.telemetry.explain import (SCOPE_VOCABULARY,
                                                 scope_table_from_hlo)
    # the kernels and the trainer's attention choice ask the backend; the
    # program is compiled for the described chip, so steer them to it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build, must_name = PROGRAMS[program]
    jitted, args = build(one_chip)
    text = jitted.lower(*args).compile().as_text()
    table = scope_table_from_hlo(text)
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    assert len(heavy) > 50
    named = [n for n in heavy if table[n]["scope"] is not None]
    assert len(named) >= 0.9 * len(heavy), sorted(set(heavy) - set(named))
    # by the compiler's own metadata, not by what the table hands down
    # from callee or users: every matmul, and most of the rest (by count
    # 67% / 69% own and 97% / 96% assigned, fused_step / serve_split)
    kinds = {m.group(1): m.group(2) for m in _HEAVY.finditer(text)}
    own = [n for n in named if not table[n]["inherited"]]
    assert all(n in own for n in heavy if kinds[n] == "convolution")
    assert len(own) >= 0.6 * len(heavy)
    found = {e["scope"] for e in table.values()}
    assert found - {None} <= set(SCOPE_VOCABULARY)
    assert set(must_name) <= found, set(must_name) - found
    backward = {e["scope"] for e in table.values() if e["backward"]}
    remat = {e["scope"] for e in table.values() if e["remat"]}
    if program == "fused_step":
        assert {"mlp", "attn_qkv", "loss"} <= backward
        # the head takes its gradients in the pass that computes its
        # logits (ISSUE 51): nothing under ``loss`` is computed again
        assert "mlp" in remat and "loss" not in remat
    else:
        assert not backward - {None} and not remat - {None}


_BRANCHES = re.compile(r"\b(?:branch_computations=\{([^}]*)\}|"
                       r"(?:true|false)_computation=(%[\w.\-]+))")


def _branches(text):
    """The names of the computations some ``conditional`` branches to."""
    return {b.strip().lstrip("%") for m in _BRANCHES.finditer(text)
            for b in (m.group(1) or m.group(2)).split(",")}


_SHAPE_OF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[([\d,]*)\]", re.M)
_SCATTER = re.compile(r" = \w+\[([\d,]*)\]\S* scatter\(%[\w.\-]+, %[\w.\-]+, "
                      r"%([\w.\-]+)\)")


def _kv_scatter_updates(text, pools):
    """The number of updates of every ``scatter`` INTO a pool of the arena
    (its result holds a pool's elements, pages flattened or not) of a
    compiled module: the rows of its ``updates`` operand, ``[slots,
    lanes]``. (A sparse layer's dispatch scatters too: not a KV write.)"""
    sizes = {int(np.prod(pool.shape)) for pool in pools}
    shapes = {m.group(1): m.group(2) for m in _SHAPE_OF.finditer(text)}
    return [int(shapes[m.group(2)].split(",")[0])
            for m in _SCATTER.finditer(text)
            if int(np.prod(list(map(int, m.group(1).split(","))))) in sizes]


_GATHER = re.compile(r" = \w+\[([\d,]*)\]\S* gather\(.*op_name=\"[^\"]*/"
                     r"attn_core/")


def _attn_core_gathers(text):
    """The elements of every ``gather`` under ``attn_core`` in a compiled
    module: what ``paged_attention_xla`` copies of the pools, ``[rows,
    pages, page, lanes]`` a pool and layer."""
    return [int(np.prod(list(map(int, m.group(1).split(",")))))
            for m in _GATHER.finditer(text)]


def _check_typed_decode_reads_through_the_kernel(text, layers, nb, mb,
                                                 xla_read):
    """A typed stack's DECODE program (ISSUE 61): the paged kernel once an
    attention layer under ``attn_core`` by the name ``paged_attn_decode``,
    NO kernel whose name begins ``paged_attn_lse`` (a trace's readers take
    that for a split step's history), and no gather of a page table's worth
    of tokens under ``attn_core`` — which ``xla_read``, the XLA reader
    alone under that scope compiled for the same chip, does hold."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    table = scope_table_from_hlo(text)
    kernels = [n for n in table if n.startswith("paged_attn")]
    assert len(kernels) == layers and all(
        n.startswith("paged_attn_decode") and
        table[n]["scope"] == "attn_core" for n in kernels), kernels
    assert "paged_attn_lse" not in text
    wide = nb * mb * 128
    assert not [g for g in _attn_core_gathers(text) if g >= wide]
    assert [g for g in _attn_core_gathers(xla_read) if g >= wide]


def _top_slots(step, nb=64):
    """The most updates a 64-row program's KV scatter may perform: its top
    capacity, the rows of a decode step. ``step``: an entry of
    ``_SERVE_STEPS`` / ``_LATENT_STEPS``."""
    cb, _, capacities = step[:3]
    return capacities[-1] if capacities else nb * cb


@pytest.mark.parametrize("kind", list(_SERVE_STEPS))
def test_no_serve_step_moves_the_arena(kind, one_chip, no_persistent_cache,
                                       monkeypatch):
    """The serving cell's three 64-row step programs: NO arena-shaped
    ``copy`` anywhere in the module, ENTRY included — the scatter writes
    the pools in the layout the kernels read, so the decode program's
    carry (write then kernel read, every layer) aliases and no program
    relays a pool on entry or exit (head-major pools took 6 / 4 / 4 such
    copies and 3.2 GB of temporaries at twelve layers: ISSUE 34,
    docs/kernels.md) — and temporaries far under a pool's size. The split
    step's history goes through the paged kernel under ``attn_history``,
    over a read-only arena: one branch a capacity, none of which writes
    it; with the token-wise sublayers over 2,048 packed slots where the
    rows hold 8,192, it counts at most 0.35 of the row form's FLOPs
    (3.593e12 at two layers, this compiler's own count of the same program
    with ``token_capacities=()``; measured 0.254: the matmuls a quarter,
    attention as it was)."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jitted, args = _serve_step(one_chip, kind)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    arena = args[1]["k"]
    shape = "bf16[" + ",".join(map(str, arena.shape)) + "]"
    arena_copy = re.compile(rf" = {re.escape(shape)}\S* copy\(")
    copies = [line.strip()[:160] for line in text.splitlines()
              if arena_copy.search(line)]
    assert not copies, copies
    # ... and the pattern can match: a pool relaid and relaid back, for
    # the same chip, holds such a copy
    relaid = jax.jit(lambda a: jax.lax.optimization_barrier(
        a.transpose(2, 0, 1)).transpose(1, 2, 0)).lower(arena).compile()
    assert arena_copy.search(relaid.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < \
        _SERVE_STEPS[kind][3]
    # the KV write scatters the slots the tokens were packed into, not
    # the rows' 8,192 (ISSUE 36)
    updates = _kv_scatter_updates(text, args[1].values())
    assert len(updates) == 2 and \
        max(updates) <= _top_slots(_SERVE_STEPS[kind]), updates
    if kind != "split":
        return
    # one instance of the layer loop a capacity, in ONE executable (the
    # layers are one scan: one kernel call a row group)
    two, args = _serve_step(one_chip, kind, _TWO_RUNGS)
    _check_split_groups("uniform", compiled, text, "paged_attn_lse", 1,
                        two.lower(*args).compile())
    assert compiled.cost_analysis()["flops"] <= 0.35 * 3.593e12


@pytest.mark.parametrize("kind", list(_SERVE_STEPS))
def test_step_program_carries_the_fed_tokens_in_place(kind, one_chip,
                                                      no_persistent_cache,
                                                      monkeypatch):
    """The engine's OWN step program (``engine_v2._step_program``: what
    ``_step_fn`` jits, the packed vector in, the slot buffer riding in the
    donated arena; ISSUE 44) at the serving cell's 64 rows: it compiles for
    the chip, the buffer is read by a gather and written by ONE scatter of
    64 updates under ``sample``, the pools are
    moved no more than without it (no arena-shaped copy, the same bound on
    temporaries) and every arena entry is updated in place (aliased with
    its donated input)."""
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.ops import paged_attention as pa
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = _mistral_2l()
    cb, fresh, capacities, most_temp = _SERVE_STEPS[kind]
    nb, mb, slots = 64, 32, 64
    fn = engine_v2._step_program(model, nb, cb, mb, ("argmax",), fresh,
                                 capacities, True, None)

    def make_arena():
        arena = pa.init_arena(model.num_layers, model.kv_heads, 512, 128,
                              model.head_dim, jnp.bfloat16)
        arena[engine_v2.FED_TOKENS] = jnp.zeros((slots + 1,), jnp.int32)
        return arena
    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(make_arena))
    packed = jax.ShapeDtypeStruct((nb * cb + 3 * nb + nb * mb + 2,),
                                  jnp.int32, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        _abstract_params(model, one_chip), arena, packed, rng).compile()
    text = compiled.as_text()
    pool = arena["k"]
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    assert not re.search(rf" = {re.escape(shape)}\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < most_temp
    (updates,) = _kv_scatter_updates(text, [arena[engine_v2.FED_TOKENS]])
    assert updates == nb
    # ... written under ``sample``, where the tokens are made
    assert re.search(rf"= s32\[{slots + 1}\]\S* scatter\(.*"
                     rf"op_name=\"jit\(fn\)/sample/scatter\"", text)
    # three arena entries, each an output that aliases its donated input
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliases.group(1).count("-alias)") == 3, aliases.group(1)


# -- the latent stack (GigaChat3.1 / DeepSeek-V3 widths), two layers ---------

#: kind -> (chunk, fresh_prefill, token capacities, most temporaries at two
#: layers). The split step's are the row form of one layer's attention: the
#: absorbed queries [64, 128, 64, 640] and the kernel's latent-space output
#: [64, 128, 64, 512] (0.67 + 0.54 GB), the expanded q / k / v of the
#: chunk (3 x 0.20 GB) and its scores (docs/kernels.md)
_LATENT_STEPS = {
    "decode": (1, False, (), 0.4e9),
    "split": (128, "split", (512, 1024, 2048), 4.0e9),
    "fresh": (128, "fresh", (2048,), 4.0e9),
}


def _latent_2l():
    """One dense and one sparse latent layer at the published widths, 16
    of the 256 experts held (benchmark/configs/gigachat3.1-l5-e16-serve)."""
    from deepspeed_tpu.models.hf_loader import config_from_hf
    return config_from_hf({
        "model_type": "deepseek_v3", "vocab_size": 16032,
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_hidden_layers": 2,
        "num_attention_heads": 64, "num_key_value_heads": 64,
        "n_shared_experts": 1, "n_routed_experts": 16,
        "routed_scaling_factor": 2.5, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 192,
        "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 8,
        "topk_group": 4, "num_experts_per_tok": 8, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 100000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "rope_type": "yarn"},
        "expert_share": {"router_experts": 256, "first_expert": 0}})


def _typed_step(one_chip, model, step, mb, make_arena, nb=64):
    """An ``nb``-row (64) step program of a typed stack compiled for the
    chip, and its text: ``step`` = (chunk, ``fresh_prefill``, capacities,
    ...) over the abstract ``make_arena()`` and a page table ``mb`` pages
    wide. NO pool-shaped copy in the module, and no KV scatter of more
    updates than the step's top capacity (a decode step: its rows)."""
    from deepspeed_tpu.inference import engine_v2
    cb, fresh, capacities = step[:3]

    def serve_step(params, arena, tokens, counts, starts, pt):
        logits, arena = engine_v2.ragged_forward(
            model, params, arena, tokens, counts, starts, pt,
            use_pallas=True, moe_fn=None, fresh_prefill=fresh,
            token_capacities=capacities)
        out, _ = engine_v2._sample_tokens(logits, ("argmax",), 1.0, 1.0,
                                          None)
        return out, arena

    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(make_arena))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = jax.jit(serve_step, donate_argnums=(1,)).lower(
        _abstract_params(model, one_chip), arena, i32(nb, cb), i32(nb),
        i32(nb), i32(nb, mb)).compile()
    text = compiled.as_text()
    for pool in arena.values():
        shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(rf" = {re.escape(shape)}\S* copy\(", line)]
        assert not copies, copies
    updates = _kv_scatter_updates(text, arena.values())
    assert len(updates) == len(arena) * (
        model.num_layers // len(set(model.layer_kinds))) and \
        max(updates) <= _top_slots(step, nb), updates
    return compiled, text


@pytest.mark.parametrize("kind", list(_SERVE_STEPS))
def test_mimo_step_scatters_its_token_slots(kind, one_chip,
                                            no_persistent_cache,
                                            monkeypatch):
    """The 64-row decode, split and fresh programs (``_SERVE_STEPS``' chunk
    and capacities) of MiMo-V2.5's stack at the published widths (benchmark/configs/mimo-v2.5-l7-e16-serve, cut to
    its first full and first window layer: the dense FFN and the held
    experts), four pools under one page table of 8 pages a row, the K
    pools wider than the head: every pool's KV scatter performs at most
    the step's top capacity of updates — 2,048 packed slots, where the
    rows hold 8,192 — under ``kv_write``, and no pool is copied."""
    import json
    import os
    from benchmark.lib import model as model_lib
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = json.load(open(os.path.join(
        os.path.dirname(model_lib.__file__), "..", "configs",
        "mimo-v2.5-l7-e16-serve.json")))
    model = model_lib.build_model({**conf, "num_hidden_layers": 2})
    assert model.layer_kinds == (0, 1) and model.head_dim == 192
    def make_arena():
        return pa.init_arena_typed(
            model.layer_kinds,
            {a: model.kind_kv_heads(a) for a in set(model.layer_kinds)},
            512, 128, 256, model.v_dim, jnp.bfloat16)
    compiled, text = _typed_step(one_chip, model, _SERVE_STEPS[kind], 8,
                                 make_arena)
    assert "kv_write" in {e["scope"] for e in
                          scope_table_from_hlo(text).values()}
    if kind == "split":
        _check_split_groups(
            "mimo", compiled, text, "paged_attn_lse", 2, _typed_step(
                one_chip, model, (128, "split", _TWO_RUNGS), 8,
                make_arena)[0])
    if kind != "decode":
        return
    # the decode program reads what it wrote through the kernel, a full
    # and a window layer alike; the XLA read of the full layer's pools
    # alone, for the pattern: 64 rows x 8 pages gathered
    arena = jax.eval_shape(make_arena)

    def xla_read(q, k, v, pt, starts, counts):
        with jax.named_scope("attn_core"):
            return pa.paged_attention_xla(q, k, v, pt, starts, counts,
                                          with_lse=True)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = functools.partial(on_chip, dtype=jnp.int32)
    read = jax.jit(xla_read).lower(
        on_chip((64, 1, 64, 256), jnp.bfloat16),
        *(on_chip(arena[name].shape, jnp.bfloat16) for name in ("k", "v")),
        i32((64, 8)), i32((64,)), i32((64,))).compile().as_text()
    _check_typed_decode_reads_through_the_kernel(text, 2, 64, 8, read)


def test_full_row_split_program_holds_its_ladder_on_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """The program a 16-sequence engine runs all day (PR 50): Command A+'s
    16-row split step as the cell builds it (benchmark/configs/
    command-a-plus-l4-e16-serve: four layers, window window window full, 128
    query heads on 8 KV heads, the parallel block, 16 of 128 experts held;
    1,376 pages of 128, 86 a row) with the ladder ``(512, 1024, 2048)`` a
    full-row program holds under row slots that hold the budget — three
    branches, the history kernel five times a layer (``[16, 128]`` in the
    top instance, ``[4, 128]`` / ``[8, 128]`` + ``[16, 1]`` in the two
    under it), the write-back in blocks of 512 — beside the ROW form it
    replaces: ``arguments + temporaries`` within 0.1 GB of it (measured
    13.364 against 13.362 GB) and under the chip's 15.75 GiB."""
    import types
    from benchmark.lib import model as model_lib
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.ops import paged_attention as pa
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = model_lib.load_config("command-a-plus-l4-e16-serve")
    model = model_lib.build_model(conf)
    engine = conf["engine"]
    nb, cb = engine["max_sequences"], engine["prefill_chunk"]
    mb = engine["max_seq_len"] // engine["block_size"]
    assert model.layer_kinds == (1, 1, 1, 0) and (nb, cb, mb) == (16, 128, 86)
    ladder = RaggedInferenceEngineTPU._token_capacities(
        types.SimpleNamespace(config=types.SimpleNamespace(**engine)),
        nb, cb, "split")
    assert ladder == (512, 1024, 2048)

    def make_arena():
        return pa.init_arena_typed(
            model.layer_kinds,
            {a: model.kind_kv_heads(a) for a in set(model.layer_kinds)},
            engine["num_blocks"], engine["block_size"], model.head_dim,
            model.v_dim, jnp.bfloat16)
    compiled, text = _typed_step(one_chip, model, (cb, "split", ladder), mb,
                                 make_arena, nb=nb)
    rows, _ = _typed_step(one_chip, model, (cb, "split", ()), mb, make_arena,
                          nb=nb)
    assert max(_kv_scatter_updates(text, jax.eval_shape(
        make_arena).values())) == ladder[0]
    print("full-row split program, ladder | rows: arguments + temporaries",
          _memory(compiled), _memory(rows))
    _check_three_rungs(compiled, text, "paged_attn_lse", model.num_layers,
                       rows)


@pytest.mark.parametrize("kind", list(_LATENT_STEPS))
def test_latent_step_reads_the_pool_absorbed(kind, one_chip,
                                             no_persistent_cache,
                                             monkeypatch):
    """The latent stack's 64-row decode, split and fresh programs over the
    cell's arena (2,176 pages of 128, 34 a row, pool rows of 640 lanes): the
    ``mla_decode`` kernel reads the pool (under ``attn_core`` in the decode
    program, ``attn_history`` in the split one; the fresh one reads no
    pool); NO pool-shaped copy, no KV scatter over its top capacity; and
    the decode program holds no expansion of the history to heads — no
    tensor with the context's 4,352 slots beside the 64 heads (a gathered,
    expanded K or V would be ``[64, 4352, 64, 128|192]``)."""
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = _latent_2l()
    most = _LATENT_STEPS[kind][3]
    def make_arena():
        return pa.init_arena_typed(model.layer_kinds, {2: 1}, 2176, 128, 640,
                                   0, jnp.bfloat16)
    compiled, text = _typed_step(one_chip, model, _LATENT_STEPS[kind], 34,
                                 make_arena)
    table = scope_table_from_hlo(text)
    kernels = [n for n in table if n.startswith("mla_decode")]
    # (a fresh step reads no pool: its chunk is its whole history)
    want = {"decode": "attn_core", "split": "attn_history"}.get(kind)
    assert bool(kernels) == (kind != "fresh") and \
        all(table[n]["scope"] == want for n in kernels), \
        [(n, table[n]["scope"]) for n in kernels]
    assert {"attn_latent", "moe_shared"} <= \
        {e["scope"] for e in table.values()}
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < most, temp
    if kind == "split":
        _check_split_groups(
            "latent", compiled, text, "mla_decode", 2, _typed_step(
                one_chip, model, (128, "split", _TWO_RUNGS), 34,
                make_arena)[0])
    if kind == "decode":
        expanded = re.findall(r"\[64,4352,64,\d+\]|\[64,64,4352,\d+\]",
                              text)
        assert not expanded, sorted(set(expanded))


# -- the hybrid stack (benchmark/configs/nemotron3-nano-l26-e16-serve): state
# pools beside the pages

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at the
#: four layers ``ME*M``: measured 0.15, 0.89 and 0.81 GB)
_HYBRID_STEPS = {
    "decode": (1, False, (), 0.3e9),
    "split": (128, "split", (512, 1024, 2048), 1.2e9),
    "fresh": (128, "fresh", (2048,), 1.1e9),
}


def _hybrid_step(one_chip, monkeypatch, config, cut, step, mb,
                 num_blocks=512):
    """A 64-row step program of a recurrent stack compiled for the chip:
    the configuration ``config`` with ``cut`` laid over its keys, over the
    cell's KV arena (``num_blocks`` pages), a page table ``mb`` pages wide
    (the cell's ``max_seq_len``) and its state pools (a pool a state-space layer) →
    (the model, the abstract arena, the compiled program, its text). NO
    copy of a state pool or of a KV pool anywhere in the module."""
    import json
    import os
    from benchmark.lib import model as model_lib
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.ops import ssm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = json.load(open(os.path.join(
        os.path.dirname(model_lib.__file__), "..", "configs",
        config + ".json")))
    model = model_lib.build_model({**conf, **cut})
    cb, fresh, capacities = step[:3]
    nb = 64

    def serve_step(params, arena, tokens, counts, starts, pt, slots):
        logits, arena = engine_v2.ragged_forward(
            model, params, arena, tokens, counts, starts, pt,
            use_pallas=True, moe_fn=None, fresh_prefill=fresh,
            token_capacities=capacities, slots=slots)
        out, _ = engine_v2._sample_tokens(logits, ("argmax",), 1.0, 1.0,
                                          None)
        return out, arena

    def make_arena():
        # (heads of half a tile keep their width: ``pa.pairs_heads``)
        width = engine_v2._paged_reader(
            model, engine_v2.RaggedInferenceConfig())[1]
        arena = pa.init_arena_typed(model.layer_kinds, {0: model.kv_heads},
                                    num_blocks, 128, width, model.v_dim,
                                    jnp.bfloat16)
        arena.update(ssm.init_state_pools(model, 64, jnp.bfloat16))
        return arena

    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(make_arena))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = jax.jit(serve_step, donate_argnums=(1,)).lower(
        _abstract_params(model, one_chip), arena, i32(nb, cb), i32(nb),
        i32(nb), i32(nb, mb), i32(nb)).compile()
    text = compiled.as_text()
    for pool in arena.values():
        shape = {"float32": "f32", "bfloat16": "bf16"}[str(pool.dtype)] + \
            "[" + ",".join(map(str, pool.shape)) + "]"
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(rf" = {re.escape(shape)}\S* copy\(", line)]
        assert not copies, copies
    return model, arena, compiled, text


@pytest.mark.parametrize("kind", list(_HYBRID_STEPS))
def test_hybrid_step_updates_its_state_pools_in_place(
        kind, one_chip, no_persistent_cache, monkeypatch):
    """The 64-row decode, split and fresh programs of Nemotron 3 Nano's
    stack at the published widths, cut to ``ME*M`` (two state-space layers,
    the held experts, an attention layer), over the cell's arena and two
    state pools of 65 slots of 2 MiB: NO copy of a state pool or of a KV
    pool anywhere in the module — the split program carries the pools
    through one-trip LOOPS where a conditional copied them twice
    (``engine_v2._at_capacity``) —, every one of the six ``ssm_*`` scopes
    on some instruction, the paged kernel under ``attn_history`` in the
    split program, and temporaries under the measured ones."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    cut = {"num_hidden_layers": 4, "hybrid_override_pattern": "ME*M"}
    model, arena, compiled, text = _hybrid_step(
        one_chip, monkeypatch, "nemotron3-nano-l26-e16-serve", cut,
        _HYBRID_STEPS[kind], 32)
    assert model.layer_kinds == (3, -1, 0, 3) and model.recurrent
    assert arena["ssm1"].shape == (65, 64, 64, 128) and \
        arena["ssm1"].dtype == jnp.float32 and \
        arena["conv1"].shape == (65, 3 * 6144) and "ssm2" not in arena
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"ssm_in", "ssm_conv", "ssm_scan", "ssm_state", "ssm_norm",
            "ssm_out", "moe_shared", "kv_write"} <= scopes, scopes
    kernels = [n for n in table if n.startswith("paged_attn_lse")]
    assert bool(kernels) == (kind == "split") and \
        all(table[n]["scope"] == "attn_history" for n in kernels)
    # (the split program's three instances are loop bodies, not branches)
    assert not _branches(text) or kind != "split", _branches(text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _HYBRID_STEPS[kind][3], temp
    if kind == "split":     # ... and a third loop brings no pool copy
        _check_ladder_memory(compiled, _hybrid_step(
            one_chip, monkeypatch, "nemotron3-nano-l26-e16-serve", cut,
            (128, "split", _TWO_RUNGS), 32)[2])


# -- the two-part hybrid stack (benchmark/configs/granite-4.0-h-small-l10-e36-
# serve): every layer a mixer AND the held experts

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at the
#: three layers ``mamba attention mamba``: measured 0.29, 1.49 and 1.36 GB)
_GRANITE_STEPS = {
    "decode": (1, False, (), 0.4e9),
    "split": (128, "split", (512, 1024, 2048), 1.8e9),
    "fresh": (128, "fresh", (2048,), 1.7e9),
}


@pytest.mark.parametrize("kind", list(_GRANITE_STEPS))
def test_two_part_hybrid_step_compiles_for_v5e(
        kind, one_chip, no_persistent_cache, monkeypatch):
    """The 64-row decode, split and fresh programs of Granite 4.0-H
    Small's stack at the published widths, cut to ``mamba attention mamba``
    (36 of 72 experts held in every layer), over the cell's arena and two
    state pools of 65 slots of 4 MiB: NO copy of a state pool or of a KV
    pool anywhere in the module, the six ``ssm_*`` scopes, the experts' four
    and the attention layer's on some instruction of one program, the two
    0.22 scalings and the head's 1/16 under their parts' scopes (nothing
    heavy unscoped), and temporaries under the measured ones. (At the
    cell's ten layers the three compile to 1.77 / 1.64 / 0.19 GB of
    temporaries beside 12.27 GB of arguments: PERF.md §6, PR 45.)"""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    cut = {"num_hidden_layers": 3,
           "layer_types": ["mamba", "attention", "mamba"]}
    model, arena, compiled, text = _hybrid_step(
        one_chip, monkeypatch, "granite-4.0-h-small-l10-e36-serve", cut,
        _GRANITE_STEPS[kind], 8)
    assert model.layer_kinds == (3, 0, 3) and \
        model.layer_sparse == (1, 1, 1) and model.experts_held == (0, 36)
    assert arena["ssm1"].shape == (65, 128, 64, 128) and \
        arena["ssm1"].dtype == jnp.float32 and \
        arena["conv1"].shape == (65, 3 * 8448) and "ssm2" not in arena
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"ssm_in", "ssm_conv", "ssm_scan", "ssm_state", "ssm_norm",
            "ssm_out", "moe", "moe_router", "moe_experts", "moe_shared",
            "attn_qkv", "attn_out", "kv_write", "embed",
            "lm_head"} <= scopes, scopes
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    named = [n for n in heavy if table[n]["scope"] is not None]
    assert len(named) >= 0.95 * len(heavy), sorted(set(heavy) - set(named))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _GRANITE_STEPS[kind][3], temp
    if kind == "split":
        # three one-trip loops carry the pools as two did: no pool-sized
        # copy above, and the memory of the two-rung program (at the cell's
        # ten layers: 12.27 GB of arguments + 1.77 | 1.80 GB of temporaries,
        # two | three rungs; PERF.md §6, PR 46)
        assert not _branches(text), _branches(text)
        _check_ladder_memory(compiled, _hybrid_step(
            one_chip, monkeypatch, "granite-4.0-h-small-l10-e36-serve", cut,
            (128, "split", _TWO_RUNGS), 8)[2])


# -- the selective-scan stack (benchmark/configs/jamba2-3b-l28-serve): Mamba-1
# mixers and MQA beside a dense MLP in every layer

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at the
#: three layers ``mamba attention mamba``: measured 0.19, 0.72 and 0.66 GB)
_JAMBA_STEPS = {
    "decode": (1, False, (), 0.3e9),
    "split": (128, "split", (512, 1024, 2048), 0.9e9),
    "fresh": (128, "fresh", (2048,), 0.8e9),
}


@pytest.mark.parametrize("kind", list(_JAMBA_STEPS))
def test_selective_scan_step_compiles_for_v5e(
        kind, one_chip, no_persistent_cache, monkeypatch, capsys):
    """The 64-row decode, split and fresh programs of Jamba2-3B's stack at
    the published widths, cut to ``mamba attention mamba``, over the cell's
    arena (5,504 pages, 86 a row) and two state pools of 65 slots of 320
    KiB: NO copy of a state pool or of a KV pool anywhere in the module;
    the six ``ssm_*`` scopes AND the new ``ssm_select``, the dense ``mlp``
    and the attention layer's on some instruction; the ``selective_scan``
    kernel under ``ssm_scan`` wherever rows take the chunk form, the paged
    kernel with its 2,560-row block under ``attn_history`` in the split
    program; temporaries (printed) under the measured ones. The split
    program's three instances are one-trip loops: 4 and 8 chunk rows beside
    64 rows of one query, then all 64 at the chunk's width."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    cut = {"num_hidden_layers": 3, "attn_layer_period": 2,
           "attn_layer_offset": 1}
    model, arena, compiled, text = _hybrid_step(
        one_chip, monkeypatch, "jamba2-3b-l28-serve", cut,
        _JAMBA_STEPS[kind], 86, num_blocks=5504)
    assert model.layer_kinds == (4, 0, 4) and \
        model.layer_sparse == (0, 0, 0) and model.selective
    assert arena["ssm1"].shape == (65, 16, 5120) and \
        arena["ssm1"].dtype == jnp.float32 and \
        arena["conv1"].shape == (65, 3 * 5120) and "ssm2" not in arena and \
        arena["k"].shape == (5505, 128, 128)
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"ssm_in", "ssm_conv", "ssm_select", "ssm_scan", "ssm_state",
            "ssm_norm", "ssm_out", "mlp", "attn_qkv", "attn_out",
            "kv_write", "embed", "lm_head"} <= scopes, scopes
    scans = [n for n in table if n.startswith("selective_scan")]
    # a mixer layer and instance (the one-query rows step the recurrence)
    assert len(scans) == {"decode": 0, "split": 6, "fresh": 2}[kind] and \
        all(table[n]["scope"] == "ssm_scan" for n in scans), scans
    kernels = [n for n in table if n.startswith("paged_attn_lse")]
    # the attention layer: chunk group + one-query rows in two instances,
    # all rows at the chunk's width in the third
    assert len(kernels) == (5 if kind == "split" else 0) and \
        all(table[n]["scope"] == "attn_history" for n in kernels), kernels
    assert "2560,128" in text or kind != "split"
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    named = [n for n in heavy if table[n]["scope"] is not None]
    assert len(named) >= 0.95 * len(heavy), sorted(set(heavy) - set(named))
    assert not _branches(text) or kind != "split", _branches(text)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\njamba2-3b {kind} at 3 layers: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < _JAMBA_STEPS[kind][3], \
        mem.temp_size_in_bytes


# -- the gated delta-rule stack (benchmark/configs/qwen3-next-80b-a3b-l12-e64-
# serve): delta-rule mixers and gated 256-wide GQA beside the held experts

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at ONE
#: period ``delta delta delta full``: measured 0.05, 1.38 and 1.33 GB with
#: the chunk form a kernel (PR 63; its ``[c, c]`` matrices in XLA: 0.05,
#: 1.61 and 1.43, the split program at all 12 layers 1.87 beside 12.54 GB
#: of arguments))
_DELTA_STEPS = {
    "decode": (1, False, (), 0.2e9),
    "split": (128, "split", (512, 1024, 2048), 1.7e9),
    "fresh": (128, "fresh", (2048,), 1.6e9),
}


@pytest.mark.parametrize("kind", list(_DELTA_STEPS))
def test_delta_rule_step_compiles_for_v5e(
        kind, one_chip, no_persistent_cache, monkeypatch, capsys):
    """The 64-row decode, split and fresh programs of Qwen3-Next-80B-A3B's
    stack at the published widths, cut to ONE period ``delta delta delta
    full`` with 64 of 512 experts held, over the cell's arena (5,504 pages,
    86 a row; K and V 512 lanes a token: two heads of 256, UNPADDED) and
    three state pools of 65 slots of 2 MiB: NO copy of a state pool or of a
    KV pool anywhere in the module; the five ``ssm_*`` scopes the kind
    keeps, ``delta_rule`` in place of ``ssm_scan``, ``attn_gate``,
    ``moe_shared``; the ``delta_chunk`` kernel under ``delta_rule`` once a
    delta-rule layer and instance of a chunk-width program (4, 8 and 64
    chunk rows in the split program's three, 64 in the fresh program's
    one) and never in the decode program; the paged kernel with its
    1,024-row block of 256 lanes
    under ``attn_history`` in the split program; the split program's three
    instances one-trip loops; temporaries (printed) under the measured
    ones."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    cut = {"num_hidden_layers": 4}
    model, arena, compiled, text = _hybrid_step(
        one_chip, monkeypatch, "qwen3-next-80b-a3b-l12-e64-serve", cut,
        _DELTA_STEPS[kind], 86, num_blocks=5504)
    assert model.layer_kinds == (6, 6, 6, 0) and \
        model.layer_sparse == (1,) * 4 and model.delta_rule and \
        model.num_held_experts == 64 and model.num_experts == 512
    assert arena["ssm2"].shape == (65, 32, 128, 128) and \
        arena["ssm2"].dtype == jnp.float32 and \
        arena["conv2"].shape == (65, 3 * 8192) and \
        arena["conv2"].dtype == jnp.float32 and "ssm3" not in arena and \
        arena["k"].shape == (5505, 128, 512) == arena["v"].shape
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"ssm_in", "ssm_conv", "delta_rule", "ssm_state", "ssm_norm",
            "ssm_out", "moe", "moe_shared", "attn_qkv", "attn_gate",
            "attn_out", "kv_write", "embed", "lm_head"} <= scopes, scopes
    assert "ssm_scan" not in scopes
    kernels = [n for n in table if n.startswith("paged_attn_lse")]
    assert len(kernels) == (5 if kind == "split" else 0) and \
        all(table[n]["scope"] == "attn_history" for n in kernels), kernels
    assert "1024,256" in text or kind != "split"
    chunks = [n for n in table if n.startswith("delta_chunk")]
    assert len(chunks) == {"decode": 0, "split": 9, "fresh": 3}[kind] and \
        all(table[n]["scope"] == "delta_rule" for n in chunks), chunks
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    named = [n for n in heavy if table[n]["scope"] is not None]
    # (0.93, not the other stacks' 0.95: what has no scope here are index
    # operations — the experts' gathers' ``AssumeGatherIndicesInBound`` and
    # bit-packing custom-calls, the scalar index fusions of the pools'
    # scatter loops —, and this cut has FOUR sparse layers in three
    # instances: 94.9% of the split program's 3,698 instructions are named)
    assert len(named) >= 0.93 * len(heavy), sorted(set(heavy) - set(named))
    assert not _branches(text) or kind != "split", _branches(text)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nqwen3-next {kind} at 4 layers: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < _DELTA_STEPS[kind][3], \
        mem.temp_size_in_bytes


# -- the short-convolution stack (benchmark/configs/lfm2-24b-a2b-l40-e8-serve):
# gated short convolutions and 64-wide GQA beside a dense MLP or held experts

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at the
#: four layers ``conv conv attention conv``: measured 0.28, 0.54 and 0.25 GB;
#: at all 40 layers 0.36, 0.83 and 0.47 beside 12.91 GB of arguments)
_LFM2_STEPS = {
    "decode": (1, False, (), 0.4e9),
    "split": (128, "split", (512, 1024, 2048), 0.8e9),
    "fresh": (128, "fresh", (2048,), 0.5e9),
}


@pytest.mark.parametrize("kind", list(_LFM2_STEPS))
def test_short_conv_step_compiles_for_v5e(
        kind, one_chip, no_persistent_cache, monkeypatch, capsys):
    """The 64-row decode, split and fresh programs of LFM2-24B-A2B's stack
    at the published widths, cut to ``conv conv attention conv`` with one
    leading dense layer (all three layer shapes), over the cell's arena
    (2,048 pages, 32 a row) and three pools of 65 convolution tails: NO
    ``ssm<i>`` pool, NO copy of a pool anywhere in the module, the K and V
    pools 512 lanes a token (8 heads of 64, UNPADDED); the scopes
    ``conv_mixer`` and ``conv_state`` and no ``ssm_*``; the paged kernel
    under ``attn_history`` in the split program, reading two KV heads as
    one 128-lane tile (its q block ``[.., 4, rows, 128]``); temporaries
    (printed) under the measured ones."""
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    cut = {"num_hidden_layers": 4, "num_dense_layers": 1,
           "layer_types": ["conv", "conv", "full_attention", "conv"]}
    model, arena, compiled, text = _hybrid_step(
        one_chip, monkeypatch, "lfm2-24b-a2b-l40-e8-serve", cut,
        _LFM2_STEPS[kind], 32, num_blocks=2048)
    assert model.layer_kinds == (5, 5, 0, 5) and \
        model.layer_sparse == (0, 1, 1, 1) and model.short_conv and \
        model.experts_held == (0, 8)
    assert sorted(arena) == ["conv0", "conv1", "conv2", "k", "v"] and \
        arena["conv2"].shape == (65, 2 * 2048) and \
        arena["k"].shape == arena["v"].shape == (2049, 128, 512)
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"conv_mixer", "conv_state", "mlp", "moe", "moe_router",
            "moe_experts", "attn_qkv", "attn_out", "kv_write", "embed",
            "lm_head"} <= scopes, scopes
    assert not {s for s in scopes if s and s.startswith("ssm_")}, scopes
    kernels = [n for n in table if n.startswith("paged_attn_lse")]
    assert len(kernels) == (5 if kind == "split" else 0) and \
        all(table[n]["scope"] == "attn_history" for n in kernels), kernels
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    named = [n for n in heavy if table[n]["scope"] is not None]
    assert len(named) >= 0.95 * len(heavy), sorted(set(heavy) - set(named))
    assert not _branches(text) or kind != "split", _branches(text)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nlfm2-24b-a2b {kind} at 4 layers: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < _LFM2_STEPS[kind][3], \
        mem.temp_size_in_bytes


# -- the stack with a stream four hidden states wide (benchmark/configs/
# xing4.0-29b-a4b-l6-serve): Xing4.0's published widths, ALL 64 experts

#: step -> (chunk, ``fresh_prefill``, capacities, most temporaries at the
#: two layers dense + sparse: measured 0.02 and 0.94 GB — the split
#: program's 2,048-slot rung holds the float32 stream four times over)
_WIDE_STREAM_STEPS = {
    "decode": (1, False, (), 0.1e9),
    "split": (128, "split", (512, 1024, 2048), 1.3e9),
}


@pytest.mark.parametrize("kind", list(_WIDE_STREAM_STEPS))
def test_wide_stream_step_compiles_for_v5e(kind, one_chip,
                                           no_persistent_cache, monkeypatch,
                                           capsys):
    """The 64-row decode and split programs of Xing4.0-29B-A4B's stack at
    the published widths, cut to one dense and one sparse layer with ALL 64
    experts and the whole vocabulary, over the cell's arena (2,048 pages of
    640-lane latent rows): the scopes ``hc_maps`` and ``hc_mix`` beside the
    block's own, nothing heavy unnamed, ``mla_decode`` reading the pool, NO
    copy of the pool, temporaries (printed) under the measured ones; and
    the rounds are ONE loop a sublayer: the maps of a decode program's
    four sublayers — norm, ``phi`` product, activations, a ``while`` of 20
    Sinkhorn rounds — are four loops and under 25 fusions each (written
    out 20 times they were 46 fusions a sublayer and two thirds of the
    program's instructions: PERF.md §6, PR 58)."""
    from benchmark.lib import model as model_lib
    from deepspeed_tpu.models.hf_loader import config_from_hf
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hf = model_lib.published_keys(
        model_lib.load_config("xing4.0-29b-a4b-l6-serve"))
    model = config_from_hf(dict(hf, num_hidden_layers=2))
    assert model.layer_sparse == (0, 1) and model.hc_mult == 4 and \
        model.num_held_experts == 64 and model.vocab_size == 131072

    def make_arena():
        return pa.init_arena_typed(model.layer_kinds, {2: 1}, 2048, 128, 640,
                                   0, jnp.bfloat16)
    compiled, text = _typed_step(one_chip, model, _WIDE_STREAM_STEPS[kind],
                                 32, make_arena)
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"hc_maps", "hc_mix", "attn_latent", "attn_qkv", "attn_out",
            "kv_write", "mlp", "moe", "moe_router", "moe_experts",
            "moe_shared", "embed", "lm_head"} <= scopes, scopes
    kernels = [n for n in table if n.startswith("mla_decode")]
    want = {"decode": "attn_core", "split": "attn_history"}[kind]
    assert kernels and all(table[n]["scope"] == want for n in kernels)
    heavy = [m.group(1) for m in _HEAVY.finditer(text)]
    named = [n for n in heavy if table[n]["scope"] is not None]
    assert len(named) >= 0.95 * len(heavy), sorted(set(heavy) - set(named))
    entry = re.search(r"ENTRY[^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    fusions = [line for line in entry.splitlines()
               if " fusion(" in line and "/hc_maps/" in line]
    loops = [line for line in entry.splitlines()
             if " while(" in line and "/hc_maps/" in line]
    if kind == "decode":        # (a split program's lie in its branches)
        assert len(loops) == 4 and 0 < len(fusions) <= 25 * 4, \
            (len(loops), len(fusions))
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nxing4.0-29b-a4b {kind} at 2 layers: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, hc_maps in ENTRY: "
              f"{len(loops)} loops, {len(fusions)} fusions")
    assert mem.temp_size_in_bytes < _WIDE_STREAM_STEPS[kind][3], \
        mem.temp_size_in_bytes


# -- the stack that picks its keys (benchmark/configs/glm-5.2-l5-e16-serve):
# GLM-5.2's published widths, an index pool beside the latent pool

#: step -> (chunk, ``fresh_prefill``, most temporaries at the three layers
#: ``full shared full``: measured 0.07, 1.19 and 0.34 GB — the split
#: program's are the TOP rung's picks, where all 16 rows are chunk rows: a
#: ``[16, 128, 20992]`` float32 score block is 172 MB an owner, and beside it
#: live its sortable keys, the mask, the kernel's float32 bias of that shape
#: and the tie count (a rung of 512 slots holds a quarter of each). They
#: live in HBM as the program's temporaries, sized by the largest rung)
_PICKING_STEPS = {
    "decode": (1, False, 0.3e9),
    "split": (128, "split", 2.6e9),
    "fresh": (128, "fresh", 0.6e9),
}


def _picking_cell(layers=3):
    """(model, engine keys) of the cell's configuration cut to ``layers``
    of its published list: an owner, a borrower, an owner."""
    from benchmark.lib import model as model_lib
    conf = model_lib.load_config("glm-5.2-l5-e16-serve")
    cut = dict(conf, num_hidden_layers=layers,
               mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
               indexer_types=["full", "shared", "full"][:layers])
    return model_lib.build_model(cut), conf["engine"]


@pytest.mark.parametrize("kind", list(_PICKING_STEPS))
def test_picking_step_reads_both_pools_in_place(kind, one_chip,
                                                no_persistent_cache,
                                                monkeypatch, capsys):
    """The cell's 16-row decode, split and fresh programs at GLM-5.2's
    published widths (three layers: full, shared, full) over the cell's
    arena (2,624 pages of 128, 164 a row; latent rows of 640 lanes, index
    keys of 128) compile for a described v5e: NO copy of either pool; the
    split program holds its ladder ``(512, 1024, 2048)`` as three branches
    (beside the selections' tie-count branches) with the masked walk ``mla_decode_picked`` under ``attn_history`` once a
    layer and chunk group, the scopes ``attn_index`` and ``attn_select``
    in the decode and split programs (a fresh chunk of 128 is under
    ``index_topk``: it writes its index keys and scores nothing); every
    pool's scatter at most the step's top capacity of updates; temporaries
    printed and bounded."""
    import types
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.telemetry.explain import scope_table_from_hlo
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, engine = _picking_cell()
    assert model.layer_indexer == (1, 0, 1) and model.index_topk == 2048
    nb, mb = engine["max_sequences"], \
        engine["max_seq_len"] // engine["block_size"]
    cb, fresh, most = _PICKING_STEPS[kind]
    capacities = RaggedInferenceEngineTPU._token_capacities(
        types.SimpleNamespace(config=types.SimpleNamespace(**engine)),
        nb, cb, fresh)
    assert capacities == ((512, 1024, 2048) if kind == "split" else ())

    def serve_step(params, arena, tokens, counts, starts, pt):
        logits, arena = engine_v2.ragged_forward(
            model, params, arena, tokens, counts, starts, pt,
            use_pallas=True, moe_fn=None, fresh_prefill=fresh,
            token_capacities=capacities)
        out, _ = engine_v2._sample_tokens(logits, ("argmax",), 1.0, 1.0,
                                          None)
        return out, arena

    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: pa.init_arena_typed(
            model.layer_kinds, {2: 1}, engine["num_blocks"], 128, 640, 0,
            jnp.bfloat16, index_layers=model.indexer_layers,
            index_width=model.index_head_dim)))
    assert arena["latent"].shape == (3 * 2625, 128, 640) and \
        arena[pa.INDEX_POOL].shape == (2 * 2625, 128, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = jax.jit(serve_step, donate_argnums=(1,)).lower(
        _abstract_params(model, one_chip), arena, i32(nb, cb), i32(nb),
        i32(nb), i32(nb, mb)).compile()
    text = compiled.as_text()
    for pool in arena.values():
        shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(rf" = {re.escape(shape)}\S* copy\(", line)]
        assert not copies, copies
    updates = _kv_scatter_updates(text, arena.values())
    # three latent layers and two owners' index keys
    assert len(updates) == 5 and \
        max(updates) <= (capacities[-1] if capacities else nb * cb), updates
    table = scope_table_from_hlo(text)
    scopes = {e["scope"] for e in table.values()}
    assert {"attn_latent", "moe_shared", "kv_write"} <= scopes
    assert ({"attn_index", "attn_select"} <= scopes) == (kind != "fresh"), \
        scopes
    walks = [n for n in table if n.startswith("mla_decode_picked")]
    dense = [n for n in table if n.startswith("mla_decode")
             and n not in walks]
    assert not dense, dense         # no layer reads every row
    if kind == "split":
        # the ladder's three rungs, and in each rung and owner the two
        # branches of the selection's tie count (``pa.topk_mask``)
        assert len(_branches(text)) == 3 + 2 * 2 * 3, _branches(text)
        # a layer and rung: the chunk group's history (the one-query rows
        # read by token index, in XLA)
        assert len(walks) == 3 * 3 and all(
            table[n]["scope"] == "attn_history" for n in walks), walks
    else:
        assert not walks, walks
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nglm-5.2 {kind} at 3 layers: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < most, mem.temp_size_in_bytes
    assert _memory(compiled) < 15.75 * 2 ** 30


@pytest.mark.parametrize("shape", ["rows16_one_token", "chunk_group_4x128",
                                   "packed_chunk_16x128"])
def test_picked_reads_compile_at_the_cells_shapes(shape, one_chip,
                                                  no_persistent_cache):
    """The two reads that follow the picks, alone, at the cell's call
    shapes over its latent pool (5 layers x 2,625 pages of ``[128, 640]``,
    164 pages a row, 64 heads): 16 rows of ONE query read 2,048 rows each
    by token index (``picked_attention``: a gather of ``[16, 2048, 640]``
    and the absorbed softmax over it — no pool-sized temporary); a chunk
    group of 4 rows and the top rung's 16 rows x 128 queries walk their
    pages under the picks' mask (``mla_decode(picked=)`` through Mosaic:
    its bias block ``[1, 8, 20992]`` float32 beside the page buffers in
    VMEM)."""
    from deepspeed_tpu.ops import paged_attention as pa
    n, c = {"rows16_one_token": (16, 1), "chunk_group_4x128": (4, 128),
            "packed_chunk_16x128": (16, 128)}[shape]
    pool = ((5 * 2625, 128, 640), jnp.bfloat16)
    q = ((n, c, 64, 640), jnp.bfloat16)
    pt, vec = ((n, 164), jnp.int32), ((n,), jnp.int32)
    if c == 1:
        def fn(q, pool, pt, picks, live):
            return pa.picked_attention(q, pool, pt, picks, live,
                                       v_lanes=512, scale=0.0625)
        shapes = (q, pool, pt, ((n, 2048), jnp.int32), ((n, 2048), jnp.bool_))
    else:
        def fn(q, pool, pt, starts, qcounts, picked):
            return pa.mla_decode(q, pool, pt, starts, jnp.zeros_like(starts),
                                 qcounts, v_lanes=512, scale=0.0625,
                                 picked=picked)
        shapes = (q, pool, pt, vec, vec, ((n, c, 164 * 128), jnp.bool_))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert ("mla_decode_picked" in text) == (c > 1)
    assert not re.search(r" = bf16\[13125,128,640\]\S* copy\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    # the gathered rows (42 MB) and their scores; the kernel's bias
    assert temp < (0.2e9 if c == 1 else 0.05e9 + 1.3 * n * c * 20992 * 4), \
        temp
