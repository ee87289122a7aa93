"""Flash attention kernel numerics vs XLA reference (interpret mode on CPU;
reference test pattern: tests/unit/ops/ kernel-vs-torch numerics)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import dot_product_attention
from deepspeed_tpu.ops.flash_attention import flash_attention

B, T, H, KvH, D = 2, 256, 4, 2, 64


def _qkv(seed=0, kvh=KvH, t=T):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, t, H, D)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, t, kvh, D)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, t, kvh, D)) * 0.5, jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kvh", [H, KvH])
def test_forward_matches_reference(causal, kvh):
    q, k, v = _qkv(kvh=kvh)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_backward_matches_reference():
    q, k, v = _qkv(seed=3)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(dot_product_attention(q, k, v)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


# ---------------------------------------------------------------------------
# Tile classes (PR 59): a score tile is skipped, interior or edge by its
# block indices, and only an edge tile runs the mask
# ---------------------------------------------------------------------------

#: (tq, tk, block_q, block_k, causal, window, q_offset)
GEOMETRIES = [
    (512, 512, 64, 64, True, None, 0),        # causal alone
    (512, 512, 128, 64, True, None, 0),       # block_q > block_k
    (512, 512, 64, 128, True, None, 0),       # block_q < block_k
    (512, 512, 64, 64, True, 512, 0),         # window = sequence
    (512, 512, 64, 64, True, 2048, 0),        # window > sequence
    (512, 512, 64, 64, True, 200, 0),         # window < sequence, unaligned
    (512, 512, 64, 64, True, 192, 0),         # ... a multiple of the block
    (512, 512, 64, 64, True, 64, 0),          # ... one block: none interior
    (512, 512, 64, 64, True, 1, 0),           # ... the diagonal alone
    (512, 512, 128, 64, True, 200, 0),
    (512, 512, 64, 128, True, 200, 0),
    (512, 512, 128, 32, True, 97, 0),
    (256, 768, 64, 64, True, None, 512),      # a chunk after its prefix
    (256, 768, 64, 128, True, None, 512),
    (256, 768, 128, 64, True, 300, 512),
    (256, 768, 64, 64, True, 128, 512),
    (128, 640, 64, 64, True, 100, 300),       # q_offset off the block grid
    (128, 640, 32, 128, True, None, 77),
    (128, 128, 128, 128, True, None, 0),      # one tile: an own chunk
    (128, 128, 128, 128, True, 4096, 0),
    (512, 512, 64, 64, False, None, 0),       # no mask at all
    (512, 512, 64, 64, False, 200, 0),        # a window with no diagonal
    (256, 512, 128, 64, False, 130, 100),
    (4096, 4096, 512, 512, True, 4096, 0),    # the training cells' call
]


def _geometry_id(g):
    tq, tk, bq, bk, causal, window, off = g
    return (f"{tq}x{tk}-b{bq}x{bk}-{'causal' if causal else 'full'}"
            f"-w{window}-off{off}")


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=_geometry_id)
def test_tile_classes_match_the_mask(geometry):
    """The classification against brute force, by rows (forward, dq) and
    by columns (dkv): a tile is interior IFF `_mask_scores` leaves every
    pair of it visible, skipped IFF none, and the counts sum to the grid."""
    from deepspeed_tpu.ops import flash_attention as fa
    tq, tk, bq, bk, causal, window, off = geometry
    visible = np.asarray(fa._mask_scores(
        jnp.zeros((tq, tk), jnp.float32), off, 0, causal, window)) == 0.0
    nq, nk = tq // bq, tk // bk
    tiles = visible.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    want = np.where(tiles.all(axis=(2, 3)), "interior",
                    np.where(tiles.any(axis=(2, 3)), "edge", "skipped"))

    def classes(lo, a, b, hi, n):
        assert 0 <= lo <= a <= b <= hi <= n
        return np.array(["skipped"] * lo + ["edge"] * (a - lo) +
                        ["interior"] * (b - a) + ["edge"] * (hi - b) +
                        ["skipped"] * (n - hi))

    for i in range(nq):
        got = classes(*fa._tile_ranges("k", off + i * bq, bq, bk, nk,
                                       causal, window), nk)
        assert (got == want[i]).all(), (i, got, want[i])
    for j in range(nk):
        got = classes(*fa._tile_ranges("q", j * bk, bq, bk, nq, causal,
                                       window, off), nq)
        assert (got == want[:, j]).all(), (j, got, want[:, j])
    counts = fa.tile_classes(tq, tk, bq, bk, causal, window, off)
    assert counts == tuple(int((want == c).sum())
                           for c in ("interior", "edge", "skipped"))
    assert sum(counts) == nq * nk
    # what is emitted for a range: nothing where no block enters it, the
    # tile once where every block holds exactly one, a loop otherwise
    for axis, rows in (("k", want), ("q", want.T)):
        trips = fa._class_trips(axis, tq, tk, bq, bk, causal, window, off)
        for i, n in enumerate(trips):
            counts = {r[i + 1] - r[i] for r in (
                fa._tile_ranges(axis, s, bq, bk, rows.shape[1], causal,
                                window, off)
                for s in fa._block_starts(axis, tq, tk, bq, bk, off)[0])}
            assert counts == {n} if n is not None else \
                (len(counts) > 1 or max(counts) > 1)
        assert (trips[1] != 0) == (want == "interior").any()
        assert (trips[0] != 0 or trips[2] != 0) == (want == "edge").any()
    # ... and traced scalars give the ranges host ints give
    starts = jnp.arange(nq, dtype=jnp.int32) * bq + off
    traced = jax.vmap(lambda s: jnp.stack([jnp.asarray(r, jnp.int32) for r in
                      fa._tile_ranges("k", s, bq, bk, nk, causal, window)]))(
                          starts)
    assert np.asarray(traced).tolist() == [
        list(fa._tile_ranges("k", off + i * bq, bq, bk, nk, causal, window))
        for i in range(nq)]


def _flat_qkv(seed, heads, kv_heads, t=256, d=64):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(n, t, d)) * 0.5, jnp.float32)
                 for n in (heads, kv_heads, kv_heads))


def _plain(q, k, v, causal, window):
    """The plain reference on the kernels' flat layout: (out, lse)."""
    g = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    qp = jnp.arange(q.shape[1])[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    ok = (qp >= kp) if causal else jnp.ones_like(qp >= kp)
    if window is not None:
        ok = ok & (kp > qp - window)
    s = jnp.where(ok, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", jnp.exp(s - lse[..., None]), v), lse


def _flash_flat(fa, q, k, v, causal, window, bq, bk):
    """(out, lse) of the flat kernels under the custom VJP's own rules."""
    out, (_, _, _, _, lse) = fa._flash_fwd(q, k, v, causal, 0, bq, bk,
                                           window, True, None, None)
    return out, lse[:, 0, :]


def _fwd_and_grads(fa, q, k, v, causal, window, bq, bk):
    out, lse = _flash_flat(fa, q, k, v, causal, window, bq, bk)
    grads = jax.grad(lambda *a: jnp.sum(jnp.square(fa._flash(
        *a, causal, 0, bq, bk, window, True, None, None))),
        argnums=(0, 1, 2))(q, k, v)
    return [np.asarray(x) for x in (out, lse, *grads)]


@pytest.mark.parametrize("generation", ["resident", "xl"])
@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("mask", [
    (True, None, 64, 64), (True, 160, 64, 64), (True, 256, 64, 64),
    (True, 200, 128, 64), (True, None, 64, 128)],
    ids=["causal", "window_lt_seq", "window_eq_seq", "window_bq128",
         "causal_bk128"])
def test_classed_kernels_match_reference(mask, kv_heads, generation,
                                         monkeypatch):
    """Forward, lse and dq / dk / dv of both kernel generations against
    the plain reference where interior, edge AND skipped tiles occur."""
    from deepspeed_tpu.ops import flash_attention as fa
    causal, window, bq, bk = mask
    if generation == "xl":
        monkeypatch.setattr(fa, "_resident_ok", lambda *a, **k: False)
    interior, edge, skipped = fa.tile_classes(256, 256, bq, bk, causal,
                                              window)
    assert interior and edge and skipped
    q, k, v = _flat_qkv(5, 4, kv_heads)
    got = _fwd_and_grads(fa, q, k, v, causal, window, bq, bk)
    want = (*_plain(q, k, v, causal, window),
            *jax.grad(lambda *a: jnp.sum(jnp.square(
                _plain(*a, causal, window)[0])), argnums=(0, 1, 2))(q, k, v))
    for a, b, name in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        tol = 2e-5 if name in ("out", "lse") else 5e-4
        np.testing.assert_allclose(a, np.asarray(b), rtol=tol, atol=tol,
                                   err_msg=name)


@pytest.mark.parametrize("generation", ["resident", "xl"])
@pytest.mark.parametrize("window", [None, 160, 256])
def test_interior_form_is_the_masked_form_bit_for_bit(window, generation,
                                                      monkeypatch):
    """An interior tile's result is what the masked body gives it: the
    same call with every live tile sent through the masked form (no
    interior range — the kernels' arithmetic before the classes) returns
    the same bits, forward, lse and gradients."""
    from deepspeed_tpu.ops import flash_attention as fa
    if generation == "xl":
        monkeypatch.setattr(fa, "_resident_ok", lambda *a, **k: False)
    q, k, v = _flat_qkv(7, 4, 2)
    classed = _fwd_and_grads(fa, q, k, v, True, window, 64, 64)
    ranges = fa._tile_ranges

    def no_interior(*a):
        lo, _, _, hi = ranges(*a)
        return lo, hi, hi, hi
    monkeypatch.setattr(fa, "_tile_ranges", no_interior)
    assert fa.tile_classes(256, 256, 64, 64, True, window)[0] == 0
    masked = _fwd_and_grads(fa, q, k, v, True, window, 64, 64)
    for a, b, name in zip(classed, masked, ("out", "lse", "dq", "dk", "dv")):
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("window", [None, 160, 256])
def test_multi_tile_matches_one_edge_tile(window):
    """Sixteen tiles of three classes against the SAME call at block =
    sequence (one edge tile: the masked body alone), to what the online
    softmax's order of sums allows."""
    from deepspeed_tpu.ops import flash_attention as fa
    q, k, v = _flat_qkv(9, 4, 2)
    assert fa.tile_classes(256, 256, 256, 256, True, window) == (0, 1, 0)
    tiled = _fwd_and_grads(fa, q, k, v, True, window, 64, 64)
    whole = _fwd_and_grads(fa, q, k, v, True, window, 256, 256)
    for a, b, name in zip(tiled, whole, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("call", [
    ((4096, 4096, 512, 512, 4096), (28, 8, 28)),
    ((128, 128, 128, 128, None), (0, 1, 0)),
    ((16384, 16384, 1024, 1024, 4096), (42, 28, 186))],
    ids=["cell1_4096", "own_chunk_128", "xl_16k_window"])
def test_tile_counters_at_trace_time(call):
    """``flash/tiles_*`` grow by one head's tiles when a kernel call is
    TRACED (a program build), whatever the batch and heads."""
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.telemetry.registry import registry
    (tq, tk, bq, bk, window), want = call
    names = [f"flash/tiles_{c}" for c in ("interior", "edge", "skipped")]
    q = jax.ShapeDtypeStruct((8, tq, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, tk, 128), jnp.bfloat16)

    def delta(fn, *args):
        before = [registry.counter(n).value for n in names]
        jax.eval_shape(fn, *args)
        return tuple(int(registry.counter(n).value - b)
                     for n, b in zip(names, before))

    assert fa.tile_classes(tq, tk, bq, bk, True, window) == want
    assert delta(lambda *a: fa._fwd(*a, 0.1, True, 0, bq, bk, window, True),
                 q, kv, kv) == want
    lse = jax.ShapeDtypeStruct((8, 1, tq), jnp.float32)
    assert delta(lambda q, k, v, lse: fa._bwd(
        q, k, v, q, lse, q, 0.1, True, 0, bq, bk, window, True),
        q, kv, kv, lse) == want


def test_unsupported_shape_falls_back():
    # T=100 not divisible by any block — must fall back, still correct
    q, k, v = _qkv(t=96)
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("topo", [dict(data=8), dict(data=2, model=2, seq=2),
                                  dict(data=2, seq=4)])
def test_sharded_flash_matches_reference(topo, devices):
    """flash_attention_sharded under a multi-device mesh (shard_map over
    batch/model/seq axes) must match local attention — covers the
    Ulysses-via-flash path and the DP batch sharding."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_sharded
    from deepspeed_tpu.parallel.mesh import build_mesh
    build_mesh(**topo)
    q, k, v = _qkv(seed=11)
    ref = dot_product_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b, c: flash_attention_sharded(
        a, b, c, block_q=64, block_k=64, interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [
    (8, 2, dict(data=2, model=2, seq=2)),   # GQA: kv 2 < model*seq 4
    (8, 2, dict(data=2, seq=4)),            # GQA: kv 2 < sp 4
    (2, 2, dict(data=2, model=2, seq=2)),   # MHA: q itself indivisible
])
def test_sharded_flash_uneven_heads(shape, devices):
    """The Pallas wrapper keeps the full head split for indivisible head
    counts via the uneven-head treatment (same as parallel/ulysses) —
    values AND grads match local attention; no degrade to model-only."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_sharded
    from deepspeed_tpu.parallel.mesh import build_mesh
    import jax.numpy as jnp
    h, kvh, topo = shape
    build_mesh(**topo)
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(2, 128, h, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, kvh, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, kvh, 32)), jnp.float32)
    ref = dot_product_attention(q, k, v, causal=True)
    fn = lambda a, b, c: flash_attention_sharded(
        a, b, c, block_q=64, block_k=64, interpret=True)
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gref = jax.grad(lambda a, b, c: jnp.sum(
        dot_product_attention(a, b, c, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gout = jax.jit(jax.grad(lambda a, b, c: jnp.sum(fn(a, b, c) ** 2),
                            argnums=(0, 1, 2)))(q, k, v)
    for gr, go in zip(gref, gout):
        np.testing.assert_allclose(np.asarray(go), np.asarray(gr),
                                   rtol=5e-5, atol=5e-5)


def test_chunked_cross_entropy_matches_full():
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import (chunked_cross_entropy,
                                                  cross_entropy_loss,
                                                  forward_hidden, init_params,
                                                  lm_logits)
    cfg = llama3_config("tiny", max_seq_len=64, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(2, 64), dtype=np.int32))
    labels = jnp.roll(tok, -1, axis=1)
    x, _ = forward_hidden(cfg, params, tok)
    full = cross_entropy_loss(lm_logits(cfg, params, x), labels)
    chunked = chunked_cross_entropy(cfg, params, x, labels, chunk_size=16)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-6)

    # grads must match too (the whole point is backward memory)
    def lf(p):
        x, _ = forward_hidden(cfg, p, tok)
        return chunked_cross_entropy(cfg, p, x, labels, chunk_size=16)

    def lref(p):
        x, _ = forward_hidden(cfg, p, tok)
        return cross_entropy_loss(lm_logits(cfg, p, x), labels)

    gf = jax.grad(lf)(params)
    gr = jax.grad(lref)(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def _ce_case(case, dtype=jnp.float32):
    """(cfg, head params, hidden, targets) of one head form at toy widths."""
    import dataclasses
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    # the fp16 case is a step's worth of tokens (8 x 2,048 live targets) at a
    # trainer's init_std: the mean's gradients, 1/16,384 of O(|w|), lie
    # under float16's normal range and only the loss scale holds them up
    fp16 = case == "fp16_loss_scale"
    b, t = (8, 2048) if fp16 else (2, 64)
    cfg = llama3_config("tiny", max_seq_len=t, vocab_size=256)
    cfg = dataclasses.replace(
        cfg, init_std=0.02 if fp16 else 0.3, tie_embeddings=case == "tied",
        logit_softcap=5.0 if case == "softcap" else 0.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    head = {"embed": params["embed"]} if cfg.tie_embeddings else \
        {"lm_head": params["lm_head"]}
    if case == "bias":
        head["lm_head_bias"] = jnp.asarray(rng.normal(size=(256,)),
                                           jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, t, cfg.hidden_size)), jnp.float32)
    tgt = rng.integers(0, 256, size=(b, t), dtype=np.int32)
    if case == "some_ignored":      # a whole chunk dead, and a ragged tail
        tgt[:, 16:32] = -100
        tgt[1, 50:] = -100
    elif case == "all_ignored":
        tgt[:] = -100
    head, x = jax.tree.map(lambda a: a.astype(dtype), (head, x))
    return cfg, head, x, jnp.asarray(tgt)


@pytest.mark.parametrize("logits_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["untied", "tied", "bias", "softcap",
                                  "some_ignored", "all_ignored", "scaled",
                                  "fp16_loss_scale"])
def test_chunked_cross_entropy_grads_match_dense(case, logits_dtype):
    """Loss AND d/dx, d/dW, d/dbias of the scanned head equal the dense
    head's, whatever the head's form, the mask or the upstream cotangent
    (the chunk's gradients are taken in the forward rule and only scaled
    in the backward one) — a float16 head under a 2^16 loss scale included:
    what the forward rule rounds to float16 is the chunk SUM's gradient,
    and cotangent / live meets it in float32."""
    from deepspeed_tpu.models.transformer import (chunked_cross_entropy,
                                                  cross_entropy_loss,
                                                  lm_logits)
    fp16 = case == "fp16_loss_scale"
    cfg, head, x, tgt = _ce_case(case, jnp.float16 if fp16 else jnp.float32)
    scale = {"scaled": 0.37, "fp16_loss_scale": 2.0 ** 16}.get(case, 1.0)
    chunk = x.shape[1] // 4

    def chunked(head, x):
        return scale * chunked_cross_entropy(cfg, head, x, tgt,
                                             chunk_size=chunk,
                                             logits_dtype=logits_dtype)

    def dense(head, x):
        return scale * cross_entropy_loss(lm_logits(cfg, head, x), tgt)

    lc, gc = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(head, x)
    # the dense head in float32, on the same (float16-valued) numbers
    ld, gd = jax.value_and_grad(dense, argnums=(0, 1))(*jax.tree.map(
        lambda a: a.astype(jnp.float32), (head, x)))
    # bf16 logits round at 2^-9 of |logit| <= ~10 here
    tol = 1e-5 if logits_dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(float(lc), float(ld), rtol=tol, atol=tol)
    # the primal (no gradient asked) is the same number
    np.testing.assert_allclose(float(jax.jit(chunked)(head, x)), float(lc),
                               rtol=1e-6)
    assert jax.tree.structure(gc) == jax.tree.structure(gd)
    # a float16 gradient rounds at 2^-11 of a chunk's own value, no lower
    gtol, gatol = (max(tol, 2e-3), max(tol, 1e-3)) if fp16 else (tol, tol)
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(gd)):
        assert a.dtype == (jnp.float16 if fp16 else b.dtype)
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(
            a, b, rtol=gtol,
            atol=gatol * max(np.abs(b).max(), 1e-30))
    if case == "all_ignored":
        assert float(lc) == 0.0
        assert all(not np.asarray(g).any() for g in jax.tree.leaves(gc))


def test_chunked_cross_entropy_bf16_head_grads():
    """A bf16 head (the training cells' dtype): gradients come back in the
    head's and the hidden's own dtype, the chunks' dW summed in float32."""
    from deepspeed_tpu.models.transformer import (chunked_cross_entropy,
                                                  cross_entropy_loss,
                                                  lm_logits)
    cfg, head, x, tgt = _ce_case("bias", jnp.bfloat16)
    gc = jax.jit(jax.grad(lambda h, x: chunked_cross_entropy(
        cfg, h, x, tgt, chunk_size=16, logits_dtype=jnp.bfloat16),
        argnums=(0, 1)))(head, x)
    gd = jax.grad(lambda h, x: cross_entropy_loss(lm_logits(cfg, h, x), tgt),
                  argnums=(0, 1))(*jax.tree.map(
                      lambda a: a.astype(jnp.float32), (head, x)))
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(gd)):
        assert a.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b)
        np.testing.assert_allclose(a, b, atol=3e-2 * np.abs(b).max())


def _head_dots(jaxpr, vocab, times=1):
    """(dot_generals with a ``vocab``-sized dimension, each counted once a
    trip of the scans around it; checkpoint equations) in a jaxpr."""
    dots = remats = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            dots += times
        remats += eqn.primitive.name in ("checkpoint", "remat", "remat2")
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            d, r = _head_dots(sub, vocab, inner)
            dots, remats = dots + d, remats + r
    return dots, remats


@pytest.mark.parametrize("case", ["untied", "tied", "softcap"])
def test_chunked_cross_entropy_runs_three_head_matmuls(case):
    """The mechanism, on the CPU: a differentiated chunk holds THREE
    head-sized matmuls (logits, dx, dW) and nothing rematerialised — the
    checkpointed body held four — and the plain call holds one."""
    from deepspeed_tpu.models.transformer import chunked_cross_entropy
    cfg, head, x, tgt = _ce_case(case)
    chunks = 4

    def loss(head, x):
        return chunked_cross_entropy(cfg, head, x, tgt,
                                     chunk_size=64 // chunks)

    grad = jax.grad(loss, argnums=(0, 1))
    assert _head_dots(jax.make_jaxpr(grad)(head, x).jaxpr, 256) == \
        (3 * chunks, 0)
    assert _head_dots(jax.make_jaxpr(loss)(head, x).jaxpr, 256) == \
        (chunks, 0)
    # every matmul of both rules still carries the scope the benchmark's
    # loss_ms_per_step reads, and nothing reads as recomputed
    import re
    from deepspeed_tpu.telemetry.explain import scope_of_op_name
    text = jax.jit(grad).lower(head, x).compile().as_text()
    dots = set(re.findall(r'op_name="([^"]*dot_general)"', text))
    assert len(dots) >= 2 and all(
        scope_of_op_name(n)["scope"] == "loss" for n in dots)
    assert "rematted_computation" not in text


# ---------------------------------------------------------------------------
# XL (KV-blocked-grid) kernels — the long-context path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kvh", [H, KvH])
def test_xl_forward_matches_reference(causal, kvh, monkeypatch):
    """Force the XL dispatch (as if T were past the VMEM ceiling) and
    check numerics against the XLA reference."""
    from deepspeed_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_resident_ok", lambda *a, **k: False)
    q, k, v = _qkv(kvh=kvh)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = fa.flash_attention(q, k, v, causal=causal, block_q=64,
                             block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_xl_backward_matches_reference(causal, monkeypatch):
    from deepspeed_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_resident_ok", lambda *a, **k: False)
    q, k, v = _qkv(seed=7)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            dot_product_attention(q, k, v, causal=causal)))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(fa.flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64,
            interpret=True)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_xl_sliding_window_matches_reference(monkeypatch):
    from deepspeed_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_resident_ok", lambda *a, **k: False)
    q, k, v = _qkv(seed=9)
    ref = dot_product_attention(q, k, v, causal=True, window=96)
    out = fa.flash_attention(q, k, v, causal=True, window=96,
                             block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda *a: jnp.sum(jnp.square(
        dot_product_attention(*a, causal=True, window=96))),
        argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: jnp.sum(jnp.square(fa.flash_attention(
        *a, causal=True, window=96, block_q=64, block_k=64,
        interpret=True))), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_long_seq_routes_to_xl_kernel():
    """Past the VMEM ceiling the real dispatch must pick the XL path (the
    resident BlockSpecs would demand tk*d*2 bytes of VMEM and fail)."""
    from deepspeed_tpu.ops.flash_attention import _resident_ok
    assert _resident_ok(2048, 2048, 128)
    assert not _resident_ok(32768, 32768, 128)
    # numerics at a (scaled-down) 'long' length through the public API
    q, k, v = _qkv(seed=11, t=512)
    from deepspeed_tpu.ops import flash_attention as fa
    ref = dot_product_attention(q, k, v, causal=True)
    orig = fa._VMEM_PER_TENSOR
    try:
        fa._VMEM_PER_TENSOR = 16 * 1024   # force XL at t=512
        out = fa.flash_attention(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    finally:
        fa._VMEM_PER_TENSOR = orig
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
