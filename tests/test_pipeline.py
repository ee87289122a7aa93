"""Pipeline parallelism tests (reference: tests/unit/runtime/pipe/)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import gpt2_config
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.engine import initialize

VOCAB, SEQ = 256, 32


def _batches(n, b=8, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, size=(b, SEQ),
                                       dtype=np.int32)}
            for _ in range(n)]


def _cfg(stages, micro, gas, stage_zero=1):
    return {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 2e-3}},
        "zero_optimization": {"stage": stage_zero},
        "pipeline": {"stages": stages},
    }


def test_pipeline_partition_specs():
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.models.transformer import partition_specs
    from deepspeed_tpu.runtime.pipe.pipeline import pipeline_partition_specs
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    base = partition_specs(model, zero_stage=0)
    piped = pipeline_partition_specs(base, 2)
    assert piped["layers"]["attn"]["wq"][0] == "pipe"
    assert piped["embed"]["tokens"] == base["embed"]["tokens"]


def test_pipeline_matches_dp(devices):
    """PP=2 over 4 microbatches must match plain DP training losses."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    data = _batches(8)   # 2 steps x 4 micros

    # baseline: dp=8, gas=4
    build_mesh(data=8)
    e0, *_ = initialize(model=model, config=_cfg(1, 1, 4),
                        rng=jax.random.PRNGKey(7))
    it = iter(data)
    base_losses = [float(e0.train_batch(it)) for _ in range(2)]

    # pipeline: pipe=2 x data=4, same global batch (micro 2 per dp rank x
    # dp_world 4 = 8 per micro), 4 microbatches
    build_mesh(data=4, pipe=2)
    e1, *_ = initialize(model=model, config=_cfg(2, 2, 4),
                        rng=jax.random.PRNGKey(7))
    it = iter(data)
    pipe_losses = [float(e1.train_batch(it)) for _ in range(2)]
    np.testing.assert_allclose(pipe_losses, base_losses, rtol=3e-4,
                               atol=3e-4)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_balanced_partition_uneven_layers(schedule, devices):
    """VERDICT r3 #8: L %% S != 0 (here 3 layers over 2 stages) runs via
    the balanced masked-padding split and MATCHES the data-parallel
    baseline's losses — the dummy padding layer is value-identity with
    zero grads, and the tick critical path is ceil(L/S) (what the
    reference's partition_balanced minimizes, pipe/module.py:393)."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                        num_layers=3)
    data = _batches(8, seed=11)

    build_mesh(data=8)
    e0, *_ = initialize(model=model, config=_cfg(1, 1, 4),
                        rng=jax.random.PRNGKey(7))
    it = iter(data)
    base_losses = [float(e0.train_batch(it)) for _ in range(2)]

    build_mesh(data=4, pipe=2)
    cfg = _cfg(2, 2, 4)
    cfg["pipeline"]["schedule"] = schedule
    e1, *_ = initialize(model=model, config=cfg,
                        rng=jax.random.PRNGKey(7))
    # padded stacked layers: 4 rows, last one masked dummy
    n_stacked = jax.tree.leaves(e1.params["layers"])[0].shape[0]
    assert n_stacked == 4
    it = iter(data)
    pipe_losses = [float(e1.train_batch(it)) for _ in range(2)]
    np.testing.assert_allclose(pipe_losses, base_losses, rtol=3e-4,
                               atol=3e-4)


def test_pipeline_tied_embeddings_across_stages(devices):
    """General tied leaves (reference TiedLayerSpec, pipe/module.py:77):
    with tie_embeddings the SAME leaf serves stage-0 embedding and the
    last-stage LM head; it lives replicated over 'pipe' and its gradient
    is the psum of both uses — training must match the DP baseline."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB,
                        tie_embeddings=True)
    assert model.tie_embeddings
    data = _batches(8, seed=13)

    build_mesh(data=8)
    e0, *_ = initialize(model=model, config=_cfg(1, 1, 4),
                        rng=jax.random.PRNGKey(5))
    it = iter(data)
    base_losses = [float(e0.train_batch(it)) for _ in range(2)]

    build_mesh(data=4, pipe=2)
    cfg = _cfg(2, 2, 4)
    cfg["pipeline"]["schedule"] = "1f1b"
    e1, *_ = initialize(model=model, config=cfg,
                        rng=jax.random.PRNGKey(5))
    it = iter(data)
    pipe_losses = [float(e1.train_batch(it)) for _ in range(2)]
    np.testing.assert_allclose(pipe_losses, base_losses, rtol=3e-4,
                               atol=3e-4)


def test_pipeline_host_offload_remat_matches(devices):
    """offload_full on the PP path (stage scan names its carry 'block_in')
    must reproduce the plain-remat pipeline losses — the host round-trip
    changes residency, never math."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    data = _batches(8, seed=3)
    losses = {}
    for policy in ("full", "offload_full"):
        build_mesh(data=4, pipe=2)
        cfg = _cfg(2, 2, 4)
        cfg["activation_checkpointing"] = {"policy": policy}
        eng, *_ = initialize(model=model, config=cfg,
                             rng=jax.random.PRNGKey(7))
        it = iter(data)
        losses[policy] = [float(eng.train_batch(it)) for _ in range(2)]
    np.testing.assert_allclose(losses["offload_full"], losses["full"],
                               rtol=1e-5)


def test_pipeline_forward_backward_raises(devices):
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    build_mesh(data=4, pipe=2)
    eng, *_ = initialize(model=model, config=_cfg(2, 2, 2),
                         rng=jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="pipeline"):
        eng.forward(_batches(1)[0])


def test_pipeline_with_zero3(devices):
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    build_mesh(data=4, pipe=2)
    eng, *_ = initialize(model=model, config=_cfg(2, 2, 2, stage_zero=3),
                         rng=jax.random.PRNGKey(3))
    losses = []
    it = iter(_batches(6, seed=2))
    for _ in range(3):
        losses.append(float(eng.train_batch(it)))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_1f1b_matches_gpipe_grads(devices):
    """Explicit 1F1B backward must produce the same loss and gradients as
    the autodiff GPipe schedule (reference schedule.py:189 TrainSchedule
    vs all-fwd/all-bwd)."""
    from deepspeed_tpu.models.transformer import init_params, partition_specs
    from deepspeed_tpu.runtime.pipe.pipeline import (
        pipeline_partition_specs, pipelined_loss,
        pipelined_loss_and_grads_1f1b)

    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    mesh = build_mesh(pipe=2, data=4)
    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    M, B = 4, 8
    tokens = jnp.asarray(rng.integers(0, VOCAB, size=(M, B, SEQ),
                                      dtype=np.int32))
    labels = jnp.concatenate(
        [tokens[:, :, 1:], jnp.full_like(tokens[:, :, :1], -100)], axis=2)

    gpipe = jax.jit(lambda p: jax.value_and_grad(
        lambda p: pipelined_loss(model, p, tokens, labels,
                                 remat_policy="full", num_stages=2))(p))
    l_g, g_g = gpipe(params)

    onefb = jax.jit(lambda p: pipelined_loss_and_grads_1f1b(
        model, p, tokens, labels, scale=1.0, remat_policy="full",
        num_stages=2))
    l_f, g_f = onefb(params)

    np.testing.assert_allclose(float(l_f), float(l_g), rtol=2e-4)
    for k in g_f:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4),
            g_f[k], g_g[k])


def test_pipeline_schedule_config(devices):
    """schedule='gpipe' must disable the 1F1B grad fn; bad values raise."""
    from deepspeed_tpu.runtime.model_factory import decoder_model_spec
    from deepspeed_tpu.config import DeepSpeedTPUConfig
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    base = {"train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    cfg_1f1b = DeepSpeedTPUConfig.from_any(
        {**base, "pipeline": {"stages": 2}})
    spec = decoder_model_spec(model, cfg_1f1b)
    assert spec.pipeline_grad_fn is not None
    cfg_gpipe = DeepSpeedTPUConfig.from_any(
        {**base, "pipeline": {"stages": 2, "schedule": "gpipe"}})
    spec = decoder_model_spec(model, cfg_gpipe)
    assert spec.pipeline_grad_fn is None
    assert spec.pipeline_loss_fn is not None
    import pytest as _pytest
    with _pytest.raises(ValueError, match="schedule"):
        decoder_model_spec(model, DeepSpeedTPUConfig.from_any(
            {**base, "pipeline": {"stages": 2, "schedule": "wat"}}))


@pytest.mark.parametrize("family", ["bloom", "gemma"])
def test_pipeline_embed_semantics_match_dp(family, devices):
    """Gemma sqrt(d) embed scaling and BLOOM's word_embeddings_layernorm
    (+ALiBi) must survive the pipeline embed path: pipe=2 losses ==
    DP losses for the same weights/data."""
    from deepspeed_tpu.models.bloom import bloom_config
    from deepspeed_tpu.models.gemma import gemma_config
    mk = bloom_config if family == "bloom" else gemma_config
    model = mk("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    data = _batches(4)

    build_mesh(data=8)
    e0, *_ = initialize(model=model, config=_cfg(1, 1, 2),
                        rng=jax.random.PRNGKey(3))
    it = iter(data)
    base = [float(e0.train_batch(it)) for _ in range(2)]

    build_mesh(data=4, pipe=2)
    e1, *_ = initialize(model=model, config=_cfg(2, 1, 2),
                        rng=jax.random.PRNGKey(3))
    it = iter(data)
    piped = [float(e1.train_batch(it)) for _ in range(2)]
    np.testing.assert_allclose(base, piped, rtol=2e-4, atol=2e-4)


def test_1f1b_bloom_embed_norm_grads(devices):
    """1F1B threads BLOOM's embed_norm through the packed embed tree; its
    grads must match GPipe autodiff exactly."""
    import jax.tree_util as jtu
    from deepspeed_tpu.models.bloom import bloom_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.runtime.pipe.pipeline import (
        pipelined_loss, pipelined_loss_and_grads_1f1b)
    build_mesh(pipe=2, data=4)
    model = bloom_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    p = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (4, 2, SEQ), dtype=np.int32))
    labels = jnp.asarray(rng.integers(0, VOCAB, (4, 2, SEQ), dtype=np.int32))
    gl, gg = jax.jit(lambda q: jax.value_and_grad(
        lambda r: pipelined_loss(model, r, tokens, labels))(q))(p)
    l1, g1 = jax.jit(lambda q: pipelined_loss_and_grads_1f1b(
        model, q, tokens, labels))(p)
    np.testing.assert_allclose(float(gl), float(l1), rtol=1e-5)
    assert jtu.tree_structure(gg) == jtu.tree_structure(g1)
    for (path, a), (_, b) in zip(jtu.tree_flatten_with_path(gg)[0],
                                 jtu.tree_flatten_with_path(g1)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=str(path))


def test_pipeline_tp_dp_composition_matches_dp(devices):
    """PP=2 x TP=2 x DP=2 must reproduce plain-DP losses (embeddings
    replicate across 'model' under PP — the XLA partial-manual gather
    workaround — so the math is unchanged)."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    data = _batches(4, b=4)

    build_mesh(data=4, devices=jax.devices()[:4])
    e0, *_ = initialize(model=model, config=_cfg(1, 1, 1),
                        rng=jax.random.PRNGKey(5))
    it = iter(data)
    base = [float(e0.train_batch(it)) for _ in range(4)]

    build_mesh(pipe=2, data=2, model=2)
    cfg = _cfg(2, 1, 2)
    cfg["tensor_parallel"] = {"enabled": True, "tp_size": 2}
    e1, *_ = initialize(model=model, config=cfg,
                        rng=jax.random.PRNGKey(5))
    # dp=2 × micro=1 → each pipeline micro is 2 rows; split each 4-row
    # global batch into its two micros so both runs see the same samples
    micros = [{"input_ids": d["input_ids"][lo:lo + 2]}
              for d in data for lo in (0, 2)]
    it = iter(micros)
    piped = [float(e1.train_batch(it)) for _ in range(4)]
    np.testing.assert_allclose(base, piped, rtol=2e-4, atol=2e-4)


def test_pipeline_sp_rejected(devices):
    """PP + SP is an explicit error, not a cryptic nested-shard_map
    trace."""
    model = gpt2_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    build_mesh(pipe=2, data=2, seq=2)
    cfg = _cfg(2, 1, 1)
    cfg["sequence_parallel"] = {"size": 2}
    with pytest.raises(ValueError, match="does not compose"):
        initialize(model=model, config=cfg, rng=jax.random.PRNGKey(0))


def test_1f1b_phi_untied_head_bias_grads(devices):
    """Phi-style untied lm_head WITH bias must flow through both pipeline
    schedules: the packed head tree carries lm_head_bias, the loss includes
    it, and its grads come back under the right keys (regression: the head
    used to be threaded as a bare array, dropping the bias and KeyError-ing
    the 1F1B grads reassembly)."""
    import jax.tree_util as jtu
    from deepspeed_tpu.models.phi import phi_config
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.runtime.pipe.pipeline import (
        pipelined_loss, pipelined_loss_and_grads_1f1b)
    build_mesh(pipe=2, data=4)
    model = phi_config("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    assert model.lm_head_bias and not model.tie_embeddings
    p = init_params(model, jax.random.PRNGKey(0))
    # nonzero bias so a dropped bias changes the loss
    p["lm_head_bias"] = jax.random.normal(
        jax.random.PRNGKey(1), p["lm_head_bias"].shape, jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (4, 2, SEQ), dtype=np.int32))
    labels = jnp.asarray(rng.integers(0, VOCAB, (4, 2, SEQ), dtype=np.int32))

    # GPipe loss must equal the non-pipeline forward loss (bias included)
    from deepspeed_tpu.models import transformer as T
    flat_tok = tokens.reshape(8, SEQ)
    flat_lbl = labels.reshape(8, SEQ)
    hidden, _ = T.forward_hidden(model, p, flat_tok)
    ref = float(T.chunked_cross_entropy(model, p, hidden, flat_lbl))
    gl, gg = jax.jit(lambda q: jax.value_and_grad(
        lambda r: pipelined_loss(model, r, tokens, labels))(q))(p)
    np.testing.assert_allclose(float(gl), ref, rtol=1e-5)
    assert "lm_head_bias" in gg and np.abs(np.asarray(
        gg["lm_head_bias"])).max() > 0

    l1, g1 = jax.jit(lambda q: pipelined_loss_and_grads_1f1b(
        model, q, tokens, labels))(p)
    np.testing.assert_allclose(float(gl), float(l1), rtol=1e-5)
    assert jtu.tree_structure(gg) == jtu.tree_structure(g1)
    for (path, a), (_, b) in zip(jtu.tree_flatten_with_path(gg)[0],
                                 jtu.tree_flatten_with_path(g1)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=str(path))


@pytest.mark.parametrize("family", ["gpt2_tied", "phi_untied_bias"])
@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_scanned_head_matches_dense(schedule, family, devices):
    """A CE budget that forces chunk < t runs the scanned head — a
    custom_vjp whose forward rule sums dW over the chunks — INSIDE the
    pipeline's shard_map (under jax.grad in GPipe, under jax.vjp with the
    tail params invariant on 'pipe' in 1F1B): loss and every gradient
    equal the same schedule's with the dense head, and the non-pipelined
    dense model's."""
    import jax.tree_util as jtu
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.phi import phi_config
    from deepspeed_tpu.runtime.pipe.pipeline import (
        pipelined_loss, pipelined_loss_and_grads_1f1b)
    build_mesh(pipe=2, data=4)
    make = gpt2_config if family == "gpt2_tied" else phi_config
    model = make("tiny", max_seq_len=SEQ, vocab_size=VOCAB)
    p = T.init_params(model, jax.random.PRNGKey(0))
    if "lm_head_bias" in p:
        p["lm_head_bias"] = jax.random.normal(
            jax.random.PRNGKey(1), p["lm_head_bias"].shape, jnp.float32)
    rng = np.random.default_rng(2)
    M, B = 4, 8
    tokens = jnp.asarray(rng.integers(0, VOCAB, (M, B, SEQ), dtype=np.int32))
    labels = jnp.concatenate(
        [tokens[:, :, 1:], jnp.full_like(tokens[:, :, :1], -100)], axis=2)
    # 64 KB of float32 logits: 8 positions of a microbatch's 8 rows (1F1B),
    # 2 of all 32 rows (GPipe) — four and sixteen chunks of SEQ = 32
    budget = B * 8 * VOCAB * 4
    assert T._pick_chunk(SEQ, B, VOCAB, budget) == 8
    assert T._pick_chunk(SEQ, M * B, VOCAB, budget) == 2

    def run(ce_budget_bytes):
        if schedule == "gpipe":
            return jax.jit(jax.value_and_grad(lambda q: pipelined_loss(
                model, q, tokens, labels, num_stages=2,
                ce_budget_bytes=ce_budget_bytes)))(p)
        return jax.jit(lambda q: pipelined_loss_and_grads_1f1b(
            model, q, tokens, labels, num_stages=2,
            ce_budget_bytes=ce_budget_bytes))(p)

    def plain(q):
        hidden, _ = T.forward_hidden(model, q, tokens.reshape(M * B, SEQ))
        return T.cross_entropy_loss(T.lm_logits(model, q, hidden),
                                    labels.reshape(M * B, SEQ))

    l_s, g_s = run(budget)
    l_d, g_d = run(None)        # the default budget: the dense shortcut
    l_p, g_p = jax.jit(jax.value_and_grad(plain))(p)
    np.testing.assert_allclose(float(l_s), float(l_d), rtol=1e-5)
    np.testing.assert_allclose(float(l_s), float(l_p), rtol=1e-5)
    assert jtu.tree_structure(g_s) == jtu.tree_structure(g_d) == \
        jtu.tree_structure(g_p)
    for (path, a), (_, b), (_, c) in zip(
            *(jtu.tree_flatten_with_path(g)[0] for g in (g_s, g_d, g_p))):
        a, b, c = (np.asarray(v, np.float32) for v in (a, b, c))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6,
                                   err_msg=str(path))
        np.testing.assert_allclose(a, c, rtol=2e-3, atol=2e-4,
                                   err_msg=str(path))
