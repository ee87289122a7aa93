"""Small runtime components: eigenvalue, PLD, sparse tensors, TiledLinear,
offload_states (reference: tests/unit/runtime/ misc + offload states)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.eigenvalue import Eigenvalue, power_iteration
from deepspeed_tpu.runtime.progressive_layer_drop import (
    ProgressiveLayerDrop, apply_pld_branch, layer_keep_probs,
    pld_keep_mask)
from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor,
                                                 sparse_allreduce,
                                                 sparse_embedding_grad)
from deepspeed_tpu.runtime.tiling import tiled_linear


# ---------------------------------------------------------------------------
# eigenvalue
# ---------------------------------------------------------------------------

def test_power_iteration_quadratic():
    """For loss = 1/2 xᵀAx the Hessian is A: dominant eigenvalue known."""
    evs = np.array([5.0, 2.0, 0.5], np.float32)
    q, _ = np.linalg.qr(np.random.default_rng(0).standard_normal((3, 3)))
    A = (q * evs) @ q.T

    def loss(x):
        return 0.5 * x @ jnp.asarray(A, jnp.float32) @ x

    ev, _ = power_iteration(loss, jnp.zeros((3,), jnp.float32),
                            jax.random.PRNGKey(0), max_iter=200, tol=1e-5)
    assert abs(float(ev) - 5.0) < 0.05


def test_eigenvalue_per_layer():
    def loss(params):
        return 0.5 * (3.0 * jnp.sum(params["a"] ** 2) +
                      7.0 * jnp.sum(params["b"] ** 2))

    params = {"a": jnp.ones((4,)), "b": jnp.ones((4,))}
    out = Eigenvalue(max_iter=100, tol=1e-4).compute_eigenvalue(
        loss, params, jax.random.PRNGKey(1))
    assert abs(out["a"] - 3.0) < 0.05 and abs(out["b"] - 7.0) < 0.05


def test_eigenvalue_hvp_through_chunked_cross_entropy():
    """Forward-over-reverse runs through both rules of the scanned head's
    custom_vjp: H·v equals the dense head's."""
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import (chunked_cross_entropy,
                                                  cross_entropy_loss,
                                                  init_params, lm_logits)
    from deepspeed_tpu.runtime.eigenvalue import _hvp
    cfg = llama3_config("tiny", max_seq_len=32, vocab_size=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    head = {"lm_head": params["lm_head"], "x": jnp.asarray(
        rng.normal(size=(2, 32, cfg.hidden_size)), jnp.float32)}
    tgt = jnp.asarray(rng.integers(0, 128, size=(2, 32), dtype=np.int32))
    v = jax.tree.map(lambda a: jnp.asarray(rng.normal(size=a.shape),
                                           jnp.float32), head)

    def chunked(p):
        return chunked_cross_entropy(cfg, p, p["x"], tgt, chunk_size=8)

    def dense(p):
        return cross_entropy_loss(lm_logits(cfg, p, p["x"]), tgt)

    hc, hd = jax.jit(lambda: _hvp(chunked, head, v))(), _hvp(dense, head, v)
    for k in head:
        assert np.abs(np.asarray(hd[k])).max() > 1e-6
        np.testing.assert_allclose(np.asarray(hc[k]), np.asarray(hd[k]),
                                   rtol=1e-4, atol=1e-7)
    ev = Eigenvalue(max_iter=20, tol=1e-3).compute_eigenvalue(
        chunked, head, jax.random.PRNGKey(1))
    evd = Eigenvalue(max_iter=20, tol=1e-3).compute_eigenvalue(
        dense, head, jax.random.PRNGKey(1))
    assert ev.keys() == evd.keys()
    for k in ev:
        assert ev[k] == pytest.approx(evd[k], rel=1e-3)


# ---------------------------------------------------------------------------
# progressive layer drop
# ---------------------------------------------------------------------------

def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert pld.update_state(0) == pytest.approx(1.0)
    mid = pld.update_state(100)
    assert 0.5 < mid < 1.0
    assert pld.update_state(100000) == pytest.approx(0.5, abs=1e-3)
    assert pld.get_state()["pld_theta"] == pld.get_theta()


def test_pld_keep_probs_and_mask():
    p = np.asarray(layer_keep_probs(12, theta=0.5))
    assert p[0] > p[-1] and p[-1] == pytest.approx(0.5)
    keep, scale = pld_keep_mask(jax.random.PRNGKey(0), 12, theta=0.5)
    k = np.asarray(keep)
    assert set(np.unique(k)).issubset({0.0, 1.0})
    # kept layers scale by 1/p
    s = np.asarray(scale)
    np.testing.assert_allclose(s[k == 1], (1.0 / p)[k == 1], rtol=1e-5)
    # combine helper: dropped layer = identity
    x = jnp.ones((2, 3))
    out = apply_pld_branch(jnp.float32(0.0), x, jnp.full((2, 3), 9.0))
    np.testing.assert_array_equal(np.asarray(out), np.ones((2, 3)))


# ---------------------------------------------------------------------------
# sparse tensors
# ---------------------------------------------------------------------------

def test_sparse_tensor_roundtrip_and_dup_add():
    st = SparseTensor(indices=jnp.asarray([1, 3, 1], jnp.int32),
                      values=jnp.asarray([[1.0], [2.0], [4.0]]),
                      dense_shape=(5, 1))
    dense = np.asarray(st.to_dense())
    np.testing.assert_allclose(dense[:, 0], [0, 5, 0, 2, 0])  # dup rows add


def test_sparse_embedding_grad_matches_dense(devices):
    vocab, d = 50, 8
    tokens = jnp.asarray([[1, 4, 1], [9, 4, 2]], jnp.int32)
    dout = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, d)),
                       jnp.float32)
    st = sparse_embedding_grad(tokens, dout, vocab)
    # dense reference: grad of sum(embed[tokens] * dout) wrt table
    table = jnp.zeros((vocab, d))
    g = jax.grad(lambda t: jnp.sum(t[tokens] * dout))(table)
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(g),
                               rtol=1e-5, atol=1e-6)


def test_sparse_allreduce(devices):
    from deepspeed_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(data=8)
    vocab, d = 16, 4
    rows = jnp.asarray(np.random.default_rng(1).integers(
        0, vocab, size=(8, 2)), jnp.int32)          # per-device rows
    vals = jnp.asarray(np.random.default_rng(2).standard_normal(
        (8, 2, d)), jnp.float32)

    def f(r, v):
        st = SparseTensor(r[0], v[0], (vocab, d))
        return sparse_allreduce(st, "data").to_dense()

    out = shard_map(f, mesh=mesh, in_specs=(P("data", None),
                                            P("data", None, None)),
                    out_specs=P(None, None), check_vma=False)(rows, vals)
    dense_ref = np.zeros((vocab, d), np.float32)
    for i in range(8):
        for j in range(2):
            dense_ref[int(rows[i, j])] += np.asarray(vals[i, j]) / 8
    np.testing.assert_allclose(np.asarray(out), dense_ref, rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# tiled linear
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("in_splits,out_splits", [(1, 4), (4, 1), (2, 2)])
def test_tiled_linear_matches_dense(in_splits, out_splits):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    got = tiled_linear(x, w, b, in_splits=in_splits, out_splits=out_splits)
    ref = x @ w + b
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # differentiable (remat path)
    g = jax.grad(lambda w: jnp.sum(tiled_linear(x, w, b, in_splits,
                                                out_splits)))(w)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(jax.grad(
                                   lambda w: jnp.sum(x @ w + b))(w)),
                               rtol=2e-4, atol=2e-4)


def test_tiled_linear_rejects_bad_splits():
    with pytest.raises(ValueError, match="divisible"):
        tiled_linear(jnp.ones((2, 10)), jnp.ones((10, 6)), in_splits=3)


# ---------------------------------------------------------------------------
# offload_states / reload_states
# ---------------------------------------------------------------------------

def test_offload_reload_states(devices):
    from deepspeed_tpu.models.gpt import gpt2_config
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import initialize

    model = gpt2_config("tiny", max_seq_len=32, vocab_size=128)
    build_mesh(data=8)
    eng, *_ = initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}},
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(8, 32),
                                       dtype=np.int32)}
    l0 = float(eng.train_batch(iter([batch])))

    eng.offload_states()
    assert eng.params is None and eng.opt_state is None
    with pytest.raises(RuntimeError, match="already offloaded"):
        eng.offload_states()
    eng.reload_states()
    assert eng.params is not None
    # training continues after the round trip
    l1 = float(eng.train_batch(iter([batch])))
    assert np.isfinite(l1) and l1 < l0 + 1.0
    eng.reload_states()                       # idempotent no-op
