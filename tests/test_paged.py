"""Paged attention + ragged engine tests (reference:
tests/unit/inference/v2/ragged/ + kernels/ragged_ops tests)."""

import functools
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceEngineTPU,
                                               ragged_forward)
from deepspeed_tpu.models.llama import llama3_config
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.parallel.mesh import build_mesh


def _write_chunk(ak, av, k, v, page_table, starts, counts, **kw):
    """``write_kv`` of a chunk that arrives as rows ``[n, c, kvh, d]``."""
    return pa.write_kv(ak, av, k, v, page_table,
                       *pa.row_slots(starts, counts, k.shape[1]), **kw)


def _random_arena_state(rng, kvh=2, nb=8, bs=16, dh=128, n=3, mb=4):
    """Build an arena holding random contexts for n sequences."""
    arena = pa.init_arena(1, kvh, nb, bs, dh, jnp.float32)
    ak, av = arena["k"], arena["v"]
    pt = np.full((n, mb), nb, np.int32)
    ctxs = [5, 30, 47]                      # straddle block boundaries
    free = list(range(nb))
    for i, ctx in enumerate(ctxs):
        nblk = -(-max(ctx, 1) // bs)
        blocks = [free.pop(0) for _ in range(nblk)]
        pt[i, :nblk] = blocks
        k = rng.standard_normal((1, ctx, kvh, dh)).astype(np.float32)
        v = rng.standard_normal((1, ctx, kvh, dh)).astype(np.float32)
        ak, av = _write_chunk(ak, av, jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(pt[i:i + 1]),
                             jnp.zeros((1,), jnp.int32),
                             jnp.asarray([ctx], np.int32))
    return ak, av, pt, np.asarray(ctxs, np.int32)


def test_pallas_matches_xla_decode():
    """Pallas kernel (interpret) vs XLA gather path, single-token decode."""
    rng = np.random.default_rng(0)
    kvh, dh, h, n = 2, 128, 4, 3
    ak, av, pt, starts = _random_arena_state(rng, kvh=kvh, dh=dh, n=n)
    counts = np.ones((n,), np.int32)
    k_new = rng.standard_normal((n, 1, kvh, dh)).astype(np.float32)
    v_new = rng.standard_normal((n, 1, kvh, dh)).astype(np.float32)
    ak, av = _write_chunk(ak, av, jnp.asarray(k_new), jnp.asarray(v_new),
                         jnp.asarray(pt), jnp.asarray(starts),
                         jnp.asarray(counts))
    q = rng.standard_normal((n, 1, h, dh)).astype(np.float32)
    o_xla = pa.paged_attention_xla(jnp.asarray(q), ak, av, jnp.asarray(pt),
                                   jnp.asarray(starts), jnp.asarray(counts))
    o_pal = pa.paged_attention(jnp.asarray(q), ak, av, jnp.asarray(pt),
                               jnp.asarray(starts), jnp.asarray(counts),
                               interpret=True)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_pal),
                               rtol=1e-2, atol=1e-2)


def test_pallas_matches_xla_chunk():
    """Prefill-chunk case (c > 1) incl. a fully-padded row (counts == 0)."""
    rng = np.random.default_rng(1)
    kvh, dh, h, n, c = 2, 128, 4, 4, 8
    ak, av, pt3, starts3 = _random_arena_state(rng, kvh=kvh, dh=dh, n=3)
    nb = ak.shape[0] - 1
    pt = np.full((n, pt3.shape[1]), nb, np.int32)
    pt[:3] = pt3
    starts = np.zeros((n,), np.int32)
    starts[:3] = starts3
    counts = np.array([c, c, 3, 0], np.int32)   # ragged + padded row
    k_new = rng.standard_normal((n, c, kvh, dh)).astype(np.float32)
    v_new = rng.standard_normal((n, c, kvh, dh)).astype(np.float32)
    ak, av = _write_chunk(ak, av, jnp.asarray(k_new), jnp.asarray(v_new),
                         jnp.asarray(pt), jnp.asarray(starts),
                         jnp.asarray(counts))
    q = rng.standard_normal((n, c, h, dh)).astype(np.float32)
    o_xla = pa.paged_attention_xla(jnp.asarray(q), ak, av, jnp.asarray(pt),
                                   jnp.asarray(starts), jnp.asarray(counts))
    o_pal = pa.paged_attention(jnp.asarray(q), ak, av, jnp.asarray(pt),
                               jnp.asarray(starts), jnp.asarray(counts),
                               interpret=True)
    # compare only valid rows/positions
    for i in range(n):
        for j in range(counts[i]):
            np.testing.assert_allclose(np.asarray(o_xla)[i, j],
                                       np.asarray(o_pal)[i, j],
                                       rtol=1e-2, atol=1e-2)


def test_trash_block_isolation():
    """Padded-token writes must land in the trash block, never a live one."""
    kvh, nb, bs, dh = 1, 4, 16, 128
    arena = pa.init_arena(1, kvh, nb, bs, dh, jnp.float32)
    ak, av = arena["k"], arena["v"]
    pt = np.array([[0, 1]], np.int32)
    k = jnp.ones((1, 4, kvh, dh), jnp.float32) * 7.0
    v = jnp.ones((1, 4, kvh, dh), jnp.float32) * 7.0
    # only 2 of the 4 tokens are valid
    ak, av = _write_chunk(ak, av, k, v, jnp.asarray(pt),
                         jnp.zeros((1,), jnp.int32),
                         jnp.asarray([2], np.int32))
    a = np.asarray(ak)
    assert np.all(a[0, :2] == 7.0)           # valid writes
    assert np.all(a[0, 2:] == 0.0)           # rest of live block untouched
    assert np.all(a[1] == 0.0)               # next live block untouched
    assert np.all(a[2:nb] == 0.0)            # unrelated blocks untouched


def test_ragged_forward_matches_cached(devices):
    """Ragged paged forward == dense KV-cache forward, step by step."""
    from deepspeed_tpu.models.transformer import (forward_with_cache,
                                                  init_kv_cache, init_params)
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=64, vocab_size=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = np.random.default_rng(0).integers(0, 256, size=(1, 12),
                                            dtype=np.int32)

    bs = 8
    arena = pa.init_arena(cfg.num_layers, cfg.kv_heads, 8, bs,
                          cfg.head_dim, jnp.float32)
    cache = init_kv_cache(cfg, 1, 32, jnp.float32)
    pt = np.full((1, 4), 8, np.int32)
    pt[0, :3] = [0, 1, 2]

    # prefill 8 then decode one-by-one, both paths
    logits_r, arena = ragged_forward(
        cfg, params, arena, jnp.asarray(tok[:, :8]),
        jnp.asarray([8], np.int32), jnp.asarray([0], np.int32),
        jnp.asarray(pt))
    logits_d, cache = forward_with_cache(cfg, params,
                                         jnp.asarray(tok[:, :8]), cache,
                                         jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_d),
                               rtol=2e-3, atol=2e-3)
    for i in range(8, 12):
        logits_r, arena = ragged_forward(
            cfg, params, arena, jnp.asarray(tok[:, i:i + 1]),
            jnp.asarray([1], np.int32), jnp.asarray([i], np.int32),
            jnp.asarray(pt))
        logits_d, cache = forward_with_cache(
            cfg, params, jnp.asarray(tok[:, i:i + 1]), cache, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(logits_r),
                                   np.asarray(logits_d),
                                   rtol=2e-3, atol=2e-3)


def test_continuous_batching_matches_v1(devices):
    """Mixed-length continuous batching must produce token-for-token the
    same output as solo dense generation (VERDICT #5 'done' criterion)."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    params_rng = jax.random.PRNGKey(3)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, params_rng)

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, size=(n,), dtype=np.int32)
               for n in (5, 11, 23)]

    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 64, "prefill_chunk": 8, "max_batch_tokens": 64},
        params=params)
    outs = v2.generate(prompts, max_new_tokens=6)

    v1 = init_inference(cfg, {"dtype": "float32"}, params=params)
    for p, got in zip(prompts, outs):
        ref = v1.generate(p[None, :], max_new_tokens=6)[0]
        np.testing.assert_array_equal(got, ref[:len(p) + 6])


def test_block_reuse_after_flush(devices):
    """Flushing sequences returns pages; the arena supports more total
    sequences than fit concurrently."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=64, vocab_size=256)
    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 4, "block_size": 16,
              "max_seq_len": 32, "prefill_chunk": 16,
              "max_batch_tokens": 32})
    rng = np.random.default_rng(5)
    for wave in range(3):                   # 3 waves x 2 seqs over 4 blocks
        uids = [wave * 2, wave * 2 + 1]
        prompts = [rng.integers(0, 256, size=(10,), dtype=np.int32)
                   for _ in uids]
        logits = v2.put(uids, prompts)
        assert set(logits) == set(uids)
        for u in uids:
            v2.flush(u)
    assert v2.state.allocator.free_blocks == 4


def test_max_seq_len_enforced(devices):
    """Exceeding max_seq_len raises a clear error instead of overflowing
    the page table (review finding)."""
    import pytest
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=64, vocab_size=256)
    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 16, "block_size": 16,
              "max_seq_len": 32, "prefill_chunk": 16,
              "max_batch_tokens": 64})
    rng = np.random.default_rng(0)
    v2.put([0], [rng.integers(0, 256, size=(30,), dtype=np.int32)])
    with pytest.raises(ValueError, match="max_seq_len"):
        v2.put([0], [rng.integers(0, 256, size=(5,), dtype=np.int32)])


def test_ragged_sampling_modes(devices):
    """Temperature/top-k/top-p sampling on the ragged engine: runs, is
    reproducible per engine rng, and differs from greedy."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, size=(6,), dtype=np.int32)

    def eng():
        return RaggedInferenceEngineTPU(
            cfg, {"dtype": "float32", "num_blocks": 16, "block_size": 16,
                  "max_seq_len": 64, "prefill_chunk": 8,
                  "max_batch_tokens": 32}, params=params,
            rng=jax.random.PRNGKey(7))

    greedy = eng().generate([prompt], max_new_tokens=8)[0]
    s1 = eng().generate([prompt], max_new_tokens=8, temperature=1.0,
                        top_k=50)[0]
    s2 = eng().generate([prompt], max_new_tokens=8, temperature=1.0,
                        top_k=50)[0]
    np.testing.assert_array_equal(s1, s2)       # same rng -> reproducible
    assert len(s1) == len(greedy) == 14
    assert not np.array_equal(s1, greedy)       # sampling actually samples


def _generate_stepwise(eng, prompts, budgets, temperature=0.0, top_k=0,
                       top_p=1.0):
    """A generation loop that WAITS: the prompts are queued,
    ``step_with_budget()`` runs whatever the scheduler packs until nothing
    is queued, then every row's token is fed back at once through the
    host, until its budget is spent — in rounds, so rows enter decode
    together. The reference the pump that runs ahead (generate(), the
    serving frontend) is held to: greedy tokens are a row's own."""
    mode = ("argmax",)
    if temperature:
        mode = ("sample", int(top_k), top_p < 1.0)
        eng._temperature, eng._top_p = float(temperature), float(top_p)
    uids = list(range(len(prompts)))
    seqs = {u: [int(t) for t in p] for u, p in zip(uids, prompts)}
    left = dict(zip(uids, budgets))
    eng.scheduler.put(uids, [seqs[u] for u in uids])
    fed = True
    while fed:
        got = {}
        while (out := eng.step_with_budget(mode=mode)) is not None:
            got.update(out)
        fed = False
        for u, tok in got.items():
            seqs[u].append(tok)
            left[u] -= 1
            if left[u] <= 0:
                eng.flush(u)
            else:
                eng.scheduler.put([u], [[tok]])
                fed = True
    return [np.asarray(seqs[u], np.int32) for u in uids]


def generate_against_stepwise_with_an_eos(make_engine, prompts, budget):
    """generate() on two fresh engines of ``make_engine`` — without an eos,
    then with one that a row samples inside its stream — against
    the loop that waits: greedy tokens are a row's own, so every row is
    the reference's up to and with its first eos. The typed stacks' test
    files call this on their own fixtures."""
    from deepspeed_tpu.telemetry.registry import registry
    prompts = [[int(t) for t in p] for p in prompts]
    want = [w.tolist() for w in _generate_stepwise(
        make_engine(), prompts, [budget] * len(prompts))]
    ahead = registry.counter("dispatch/launches_ahead")
    before = ahead.value
    got = make_engine().generate(prompts, max_new_tokens=budget)
    assert [g.tolist() for g in got] == want
    assert ahead.value > before         # no program was waited for
    # the eos: the token that comes FIRST the latest in some row's stream,
    # short of the row's last (a tiny stack may repeat itself from its
    # first token on: then that one)
    tails = [w[len(p):] for w, p in zip(want, prompts)]
    cut, row = max((i, r) for r, tail in enumerate(tails)
                   for i, t in enumerate(tail[:-1]) if t not in tail[:i])
    eos = tails[row][cut]
    eng = make_engine()
    got = eng.generate(prompts, max_new_tokens=budget, eos_token_id=eos)
    assert len(got[row]) == len(prompts[row]) + cut + 1 < len(want[row])
    for g, p, tail in zip(got, prompts, tails):
        end = tail.index(eos) + 1 if eos in tail else len(tail)
        assert g.tolist() == p + tail[:end]
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == eng.config.num_blocks


#: ragged prompts of one and of three prefill chunks, in every mode
_GENERATE_VS_STEPWISE = {
    "argmax": {"temperature": 0.0},
    "top_k": {"temperature": 0.8, "top_k": 8},
    "top_p": {"temperature": 0.7, "top_p": 0.9},
}


@pytest.mark.parametrize("case", list(_GENERATE_VS_STEPWISE))
def test_generate_matches_stepwise(devices, case):
    """generate() launches ahead and keeps a decoding row's token on the
    device. Greedy: token for token the loop that waits and feeds back
    through the host. Sampled: the device key is split once a LAUNCH, and
    a short prompt's row decodes beside a long one's chunks here (no
    rounds), so two prompts draw other tokens than the rounds do — but the
    same key gives the same stream, a single prompt makes the same
    launches as the loop that waits and so draws ITS stream, and sampling
    samples."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.telemetry.registry import registry
    params = init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(6)
    eng_cfg = {"dtype": "float32", "num_blocks": 32, "block_size": 16,
               "max_seq_len": 96, "prefill_chunk": 8,
               "max_batch_tokens": 64}
    ahead = registry.counter("dispatch/launches_ahead")
    kwargs = _GENERATE_VS_STEPWISE[case]
    prompts = [rng.integers(0, 256, size=(n,), dtype=np.int32)
               for n in (7, 19)]

    def fresh():
        return RaggedInferenceEngineTPU(cfg, eng_cfg, params=params,
                                        rng=jax.random.PRNGKey(1))

    eng = fresh()
    before = ahead.value
    got = eng.generate(prompts, max_new_tokens=8, **kwargs)
    # 3 prefill launches and 7 decode launches, all but the first ahead
    assert ahead.value - before == 9
    assert eng.last_program == "decode" and eng.in_flight == 0
    assert [len(g) for g in got] == [15, 27]

    if case == "argmax":
        before = ahead.value
        want = _generate_stepwise(fresh(), prompts, [8, 8])
        assert ahead.value == before
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        return
    for g, again in zip(got, fresh().generate(prompts, max_new_tokens=8,
                                              **kwargs)):
        np.testing.assert_array_equal(g, again)
    greedy = fresh().generate(prompts, max_new_tokens=8)
    assert any(not np.array_equal(g, w) for g, w in zip(got, greedy))
    for prompt in prompts:
        (one,) = fresh().generate([prompt], max_new_tokens=8, **kwargs)
        (want,) = _generate_stepwise(fresh(), [prompt], [8], **kwargs)
        np.testing.assert_array_equal(one, want)


def test_generate_eos_truncation(devices):
    """With eos_token_id set a row is flushed at its eos; outputs end at
    (and include) the first eos."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    eng = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 96, "prefill_chunk": 8,
              "max_batch_tokens": 64}, rng=jax.random.PRNGKey(2))
    prompt = [1, 2, 3]
    outs = eng.generate([prompt], max_new_tokens=12, eos_token_id=None)
    # pick the token generated at step 3 as the fake eos: rerun with it
    fake_eos = int(outs[0][len(prompt) + 3])
    eng2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 96, "prefill_chunk": 8,
              "max_batch_tokens": 64}, params=eng.params,
        rng=jax.random.PRNGKey(2))
    outs2 = eng2.generate([prompt], max_new_tokens=12,
                          eos_token_id=fake_eos)
    assert outs2[0][-1] == fake_eos
    assert len(outs2[0]) <= len(outs[0])
    np.testing.assert_array_equal(outs2[0], outs[0][:len(outs2[0])])


def test_generate_feeds_back_where_the_arena_has_no_page(devices,
                                                        monkeypatch):
    """When the arena cannot cover a row's continuation the engine
    continues nothing (``_continue`` finds no page) and generate()'s
    feed-back stands — it raises only where the page is still missing
    when the token is queued; here a retired row's pages make room in
    time. Outputs are whole and equal the loop that waits; every page is
    free at the end."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    eng_cfg = {"dtype": "float32", "num_blocks": 5, "block_size": 8,
               "max_seq_len": 64, "prefill_chunk": 8,
               "max_batch_tokens": 64}
    eng = RaggedInferenceEngineTPU(cfg, eng_cfg, rng=jax.random.PRNGKey(0))
    # two rows of 2 pages each leave 1 free: row 0 ends after 8 tokens,
    # inside its second page; row 1's 32 tokens want 2 pages more, the
    # second of them only once row 0 has retired
    prompts, budgets = [[1] * 8, [2] * 8], [8, 24]
    real, continued = eng._continue, []

    def spy(seq, row_limits):
        continued.append(real(seq, row_limits))
        return continued[-1]

    monkeypatch.setattr(eng, "_continue", spy)
    outs = eng.generate(prompts, max_new_tokens=budgets)
    assert [len(o) for o in outs] == [16, 32]
    assert any(at is not None for at in continued)
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == 5

    ref = _generate_stepwise(
        RaggedInferenceEngineTPU(cfg, eng_cfg, params=eng.params),
        prompts, budgets)
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)


def test_stepwise_failure_does_not_leak_pages(devices):
    """If the stepwise loop dies mid-generation (arena exhausted), the
    call's sequences must be flushed — leaked pages would shrink capacity
    for every later request."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    eng = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 4, "block_size": 16,
              "max_seq_len": 128, "prefill_chunk": 8,
              "max_batch_tokens": 64}, rng=jax.random.PRNGKey(0))
    free_before = eng.state.allocator.free_blocks
    # 2 prompts x (14 + 60) tokens needs more than 4x16 pages: the engine
    # continues no row without a page, and the feed-back that then has
    # none exhausts the arena mid-run (an eos id outside the vocabulary
    # never fires)
    with pytest.raises(RuntimeError, match="arena"):
        eng.generate([[1] * 14, [2] * 14], max_new_tokens=60,
                     eos_token_id=257)
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == free_before


def test_split_history_merge_matches_paged(devices):
    """hist(pre-write arena) + within-chunk causal merged by logsumexp
    must equal the single paged read on a continuation chunk — the
    equivalence the split-prefill fast path (engine_v2.ragged_forward)
    rests on. Covers mixed batches: a fresh row (starts=0), a
    continuation row, and a decode-like row (count=1)."""
    from deepspeed_tpu.ops.paged_attention import (
        causal_attention_with_lse, init_arena, merge_attention,
        paged_attention_hist_xla, paged_attention_xla)
    rng = np.random.default_rng(0)
    kvh, bs, dh, h, c = 2, 8, 64, 4, 16
    arena = init_arena(1, kvh, num_blocks=31, block_size=bs, head_dim=dh,
                       dtype=jnp.float32)
    ak, av = arena["k"], arena["v"]
    n, mb = 3, 8
    pt = jnp.asarray(np.arange(n * mb).reshape(n, mb), jnp.int32)
    starts = jnp.asarray([0, 24, 40], jnp.int32)
    counts = jnp.asarray([16, 16, 1], jnp.int32)

    # pre-populate history for rows 1/2
    hist_k = jnp.asarray(rng.normal(size=(n, 64, kvh, dh)), jnp.float32)
    hist_v = jnp.asarray(rng.normal(size=(n, 64, kvh, dh)), jnp.float32)
    ak, av = _write_chunk(ak, av, hist_k, hist_v, pt,
                      jnp.zeros((n,), jnp.int32), starts)

    q = jnp.asarray(rng.normal(size=(n, c, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(n, c, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, c, kvh, dh)), jnp.float32)

    # reference: write then one paged read
    ak2, av2 = _write_chunk(ak, av, k, v, pt, starts, counts)
    ref = paged_attention_xla(q, ak2, av2, pt, starts, counts)

    # split: history from the PRE-write arena + within-chunk causal
    out_h, lse_h = paged_attention_hist_xla(q, ak, av, pt, starts)
    out_c, lse_c = causal_attention_with_lse(q, k, v)
    got = merge_attention(out_h, lse_h, out_c, lse_c)

    # compare only valid query rows (j < counts[i])
    for i in range(n):
        cc = int(counts[i])
        np.testing.assert_allclose(np.asarray(got)[i, :cc],
                                   np.asarray(ref)[i, :cc],
                                   rtol=2e-5, atol=2e-5, err_msg=f"row {i}")


#: row -> tokens already cached, at the serving cell's chunk (128), page
#: (128) and head geometry (4 query heads a KV head): the page boundaries
#: from both sides, a full table, and padded rows whose table is all trash
_HIST_STARTS = {"start0": 0, "start1": 1, "start127": 127, "start128": 128,
                "start129": 129, "full_table": 512, "padded_a": 0,
                "padded_b": 0}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def hist_readers(request):
    """(out, lse) of the two history readers over one arena: the paged
    kernel with ``counts = 0`` (interpret mode) and the XLA gather."""
    dtype = jnp.dtype(request.param)
    rng = np.random.default_rng(7)
    kvh, groups, dh, bs, c, mb = 2, 4, 128, 128, 128, 4
    starts = np.asarray(list(_HIST_STARTS.values()), np.int32)
    n, nb = len(starts), 10
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))                # pages out of order
    for i, (name, start) in enumerate(_HIST_STARTS.items()):
        if not name.startswith("padded"):
            for b in range(-(-start // bs)):
                pt[i, b] = next(free)
    # every page holds finite noise, the trash page too: what a row has
    # not cached must not reach its output
    shape = (nb + 1, bs, kvh * dh)
    ak = jnp.asarray(rng.standard_normal(shape), dtype)
    av = jnp.asarray(rng.standard_normal(shape), dtype)
    q = jnp.asarray(rng.standard_normal((n, c, kvh * groups, dh)), dtype)
    args = (q, ak, av, jnp.asarray(pt), jnp.asarray(starts))
    got = pa.paged_attention_with_lse(*args, jnp.zeros((n,), jnp.int32),
                                      interpret=True)
    want = pa.paged_attention_hist_xla(*args)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    return jax.tree.map(lambda a: np.asarray(a, np.float32),
                        (got, want)), tol


@pytest.mark.parametrize("row", list(_HIST_STARTS))
def test_paged_history_kernel_matches_xla_reader(hist_readers, row):
    """``paged_attention_with_lse(counts=0)`` is the split program's
    history reader: keys [0, start) and nothing else, whatever the table's
    dead entries point at."""
    ((out, lse), (out_x, lse_x)), tol = hist_readers
    i = list(_HIST_STARTS).index(row)
    live = lse_x[i] > -1e29
    assert live.all() == (_HIST_STARTS[row] > 0) and \
        live.any() == live.all()
    np.testing.assert_array_equal(lse[i] > -1e29, live)
    np.testing.assert_allclose(lse[i][live], lse_x[i][live], rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(out[i][live], out_x[i][live], rtol=tol,
                               atol=tol)
    # an empty history weighs nothing in the merge, so its out never shows
    assert np.isfinite(out[i]).all()


@pytest.mark.parametrize("c, groups, want", [
    (128, 4, 4), (128, 8, 2), (128, 16, 1),     # the three serving cells
    (32, 4, 4), (96, 4, 4), (128, 1, 16), (128, 2, 8),
    (1, 4, 1), (1, 16, 1), (8, 1, 8), (2, 4, 2)])   # one tile: the chunk
def test_small_tile_is_16_matmul_rows_or_the_whole_chunk(c, groups, want):
    """``TILE_Q`` is a function of the call's shapes: the fewest queries
    whose matmul rows fill whole bf16 sublane tiles, and it divides the
    chunk; where no such tile is smaller than the chunk the block is one
    tile (every decode program's)."""
    tile_q = pa.tile_queries(c, groups)
    assert tile_q == want and c % tile_q == 0
    assert tile_q == c or (tile_q * groups) % 16 == 0


#: live queries of the rows of one batch, at a chunk of 32 (a small tile is
#: 8 queries): a padded row, a decode row, a partial tile, a tile boundary,
#: one query past it, the whole chunk, and a decode row with no history
_LIVE_QUERIES = (0, 1, 5, 8, 9, 32, 1)


@pytest.mark.parametrize("widths", ["k128_v128", "k256_v128"])
@pytest.mark.parametrize("groups", [4, 8, 16])
@pytest.mark.parametrize("window", [None, 40])
def test_history_kernel_computes_the_live_queries(window, groups, widths):
    """``paged_attention_with_lse(counts=0, qcounts=)`` against the XLA
    history reader: a row's LIVE queries (its leading ``qcounts``) within
    the readers' tolerance, every query past them exactly zero with an lse
    of -1e30, and ``qcounts=None`` the same as ``qcounts = c``. Histories
    of 0–90 tokens over pages of 16: a window of 40 starts inside them."""
    dk, dv = (128, 128) if widths == "k128_v128" else (256, 128)
    rng = np.random.default_rng(groups + dk)
    kvh, bs, c, mb, nb = 2, 16, 32, 6, 40
    qcounts = np.asarray(_LIVE_QUERIES, np.int32)
    n = len(qcounts)
    starts = np.asarray([50, 90, 33, 64, 17, 48, 0], np.int32)
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))                # pages out of order
    for i in range(n):
        for b in range(-(-starts[i] // bs)):
            pt[i, b] = next(free)
    ak = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dk)), jnp.float32)
    av = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((n, c, kvh * groups, dk)),
                    jnp.float32)
    args = (q, ak, av, jnp.asarray(pt), jnp.asarray(starts))
    kernel = functools.partial(
        pa.paged_attention_with_lse, *args, jnp.zeros((n,), jnp.int32),
        interpret=True, window=window, scale=0.1)
    out, lse = (np.asarray(a) for a in kernel(qcounts=jnp.asarray(qcounts)))
    out_x, lse_x = (np.asarray(a) for a in pa.paged_attention_hist_xla(
        *args, window=window, scale=0.1))
    live = np.arange(c)[None] < qcounts[:, None]                  # [n, c]
    assert (out[~live] == 0).all() and (lse[~live] <= -1e29).all()
    seen = live[..., None] & (lse_x > -1e29)     # ... and sees some key
    assert seen[:6].any(axis=(1, 2)).tolist() == [False] + [True] * 5
    np.testing.assert_array_equal(lse > -1e29, seen)
    np.testing.assert_allclose(lse[seen], lse_x[seen], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[seen], out_x[seen], rtol=2e-5, atol=2e-5)
    assert np.isfinite(out).all()
    whole = [np.asarray(a) for a in kernel()]
    for a, b in zip(whole, kernel(qcounts=jnp.full((n,), c, jnp.int32))):
        np.testing.assert_array_equal(a, np.asarray(b))
    # a row computed whole and the same row computed by its live tiles
    # agree bit for bit on the live queries
    np.testing.assert_array_equal(whole[0][live], out[live])
    np.testing.assert_array_equal(whole[1][live], lse[live])


#: (rows' live queries, history lengths) of one batch at a chunk of 16 over
#: pages of 16 (a small tile is 4 queries at 4 queries a KV head): a padded
#: row, a decode row, a tile, one query past it, the whole chunk twice — a
#: history that ends mid-page, on a page's edge, of one token, none at all
_FUSED_ROWS = ((0, 40), (1, 90), (4, 33), (5, 64), (16, 1), (16, 47),
               (1, 0))


@pytest.mark.parametrize("window", [None, 8, 70])
@pytest.mark.parametrize("widths", ["k128_v128", "k256_v128"])
@pytest.mark.parametrize("with_lse", [True, False])
@pytest.mark.parametrize("heads", [1, 2, 4])
def test_kernel_fetches_a_page_once_for_its_heads(heads, with_lse, widths,
                                                  window):
    """``_paged_kernel`` at ``heads`` KV heads a program (1: a page a head,
    the walk of before PR 47; 4: all of them, the page as it lies) against
    the XLA readers — the history reader
    (``with_lse``, ``counts = 0``, rows of 0 / 1 / ``tile_q`` / all live
    queries) and the decode reader's contract (each row's own keys too) —
    over a page table padded with the trash page, K wider than V, a window
    inside a page, across pages and past every history. Every choice of
    ``heads`` gives the same bits: a head's arithmetic does not know who
    shares its fetch."""
    dk, dv = (128, 128) if widths == "k128_v128" else (256, 128)
    rng = np.random.default_rng(dk + (window or 0))
    kvh, groups, bs, c, mb, nb = 4, 4, 16, 16, 7, 40
    qcounts, starts = (np.asarray(a, np.int32) for a in zip(*_FUSED_ROWS))
    n = len(qcounts)
    counts = np.zeros_like(qcounts) if with_lse else qcounts
    pt = np.full((n, mb), nb, np.int32)             # the trash page
    free = iter(rng.permutation(nb))                # pages out of order
    for i in range(n):
        for b in range(-(-(starts[i] + counts[i]) // bs)):
            pt[i, b] = next(free)
    ak = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dk)), jnp.float32)
    av = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((n, c, kvh * groups, dk)),
                    jnp.float32)
    args = (q, ak, av, jnp.asarray(pt), jnp.asarray(starts),
            jnp.asarray(counts))

    def kernel(heads):
        out, lse = pa._paged_call(
            *args, with_lse=with_lse, interpret=True, window=window,
            scale=0.1, qcounts=jnp.asarray(qcounts), heads=heads)
        return np.asarray(out), None if lse is None else np.asarray(lse)

    out, lse = kernel(heads)
    if with_lse:
        out_x, lse_x = pa.paged_attention_hist_xla(*args[:5], window=window,
                                                   scale=0.1)
    else:
        out_x, lse_x = pa.paged_attention_xla(*args, window=window,
                                              scale=0.1, with_lse=True)
    out_x, lse_x = np.asarray(out_x), np.asarray(lse_x)
    live = np.arange(c)[None] < qcounts[:, None]                  # [n, c]
    seen = live[..., None] & (lse_x > -1e29)     # ... and sees some key
    assert seen.any(axis=(1, 2)).tolist() == [False] + [True] * 5 + \
        [not with_lse]
    assert (out[~live] == 0).all() and np.isfinite(out).all()
    np.testing.assert_allclose(out[seen], out_x[seen], rtol=2e-5, atol=2e-5)
    if with_lse:
        assert (lse[~live] <= -1e29).all()
        np.testing.assert_array_equal(lse > -1e29, seen)
        np.testing.assert_allclose(lse[seen], lse_x[seen], rtol=2e-5,
                                   atol=2e-5)
    if heads > 1:
        base, base_lse = kernel(1)
        np.testing.assert_array_equal(out, base)
        if with_lse:
            np.testing.assert_array_equal(lse, base_lse)


#: (queries a KV head, chunk, KV heads, K lanes, V lanes) of every history
#: or decode call the benchmark's cells make → the KV heads a program holds
_HEADS_PER_PROGRAM = {
    # cells 2 and 9, Mistral-7B: every row as one query and the decode
    # programs' reader; the chunk group [4 | 8, 128] and the row form
    "mistral_one_query": ((4, 1, 8, 128, 128), 8),
    "mistral_chunk": ((4, 128, 8, 128, 128), 1),
    "mistral_chunk_of_32": ((4, 32, 8, 128, 128), 4),
    # cell 4, MiMo-V2.5: K 192 padded to 256 lanes, a window layer of 8 KV
    # heads and a full layer of 4
    "mimo_window_one_query": ((8, 1, 8, 256, 128), 8),
    "mimo_full_one_query": ((16, 1, 4, 256, 128), 4),
    "mimo_window_chunk": ((8, 128, 8, 256, 128), 1),
    "mimo_full_chunk": ((16, 128, 4, 256, 128), 1),
    # cell 6, Command A+: 16 queries a KV head, the row form
    "command_a_chunk": ((16, 128, 8, 128, 128), 1),
    "command_a_one_query": ((16, 1, 8, 128, 128), 8),
    # cells 7 and 8, the hybrid stacks' few attention layers: 32 / 2 heads
    # (Nemotron 3 Nano), 32 / 8 (Granite 4.0-H Small)
    "nemotron_one_query": ((16, 1, 2, 128, 128), 2),
    "nemotron_chunk": ((16, 128, 2, 128, 128), 1),
    "granite_one_query": ((4, 1, 8, 128, 128), 8),
}


@pytest.mark.parametrize("call", list(_HEADS_PER_PROGRAM))
def test_heads_per_program_by_the_calls_shapes(call):
    """``heads_per_program`` is a function of the call's shapes alone: the
    ``hp`` every cell's calls get (a block of a decode row's few matmul
    rows takes every head, 128 rows four, a chunk's 512 and up one — the
    walk as it was), a divisor of the KV heads, and never more for a block
    of more rows."""
    (groups, c, kvh, dk, dv), want = _HEADS_PER_PROGRAM[call]
    assert pa.heads_per_program(groups * c, kvh, dk, dv, 128) == want
    got = [pa.heads_per_program(rows, kvh, dk, dv, 128)
           for rows in (1, 4, 16, 64, 256, 512, 1024, 2048, 4096, 8192)]
    assert got[0] == kvh and got[-1] == 1
    assert all(kvh % hp == 0 for hp in got)
    assert got == sorted(got, reverse=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reader", ["xla", "kernel"])
def test_split_step_matches_in_loop_write(devices, monkeypatch, reader,
                                          dtype):
    """One ``split`` step (read-only arena in the layer loop, one
    write-back after it) against the single paged read with the write
    inside the loop: same logits, and the same arena page for page, each
    layer's trash page left out. A continuation row, a fresh row, a
    decode row riding along and a padded row."""
    import functools
    from deepspeed_tpu.models.transformer import init_params
    dtype = jnp.dtype(dtype)
    cfg = llama3_config("tiny")
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init_params(cfg, jax.random.PRNGKey(3)))
    nb, bs, mb, n, c = 15, 8, 8, 4, 16
    arena = pa.init_arena(cfg.num_layers, cfg.kv_heads, nb, bs,
                          cfg.head_dim, dtype)
    rng = np.random.default_rng(5)
    starts = np.asarray([24, 0, 40, 0], np.int32)
    counts = np.asarray([16, 16, 1, 0], np.int32)
    pt = np.full((n, mb), nb, np.int32)
    pt[0, :5], pt[1, :2], pt[2, :6] = [3, 9, 1, 12, 7], [0, 5], \
        [2, 4, 6, 8, 10, 11]
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    # the history, through the program this PR leaves alone
    _, arena = ragged_forward(cfg, params, arena, toks(n, 40),
                              jnp.asarray(starts), jnp.zeros((n,), jnp.int32),
                              jnp.asarray(pt))
    step = (toks(n, c), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray(pt))
    want_logits, want = ragged_forward(cfg, params, arena, *step)
    if reader == "kernel":
        monkeypatch.setattr(pa, "paged_attention_with_lse",
                            functools.partial(pa.paged_attention_with_lse,
                                              interpret=True))
    got_logits, got = ragged_forward(cfg, params, arena, *step,
                                     use_pallas=reader == "kernel",
                                     fresh_prefill="split")
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(got_logits)[:3], f32(want_logits)[:3],
                               rtol=tol, atol=tol)
    pages = [l * (nb + 1) + b for l in range(cfg.num_layers)
             for b in range(nb)]
    for name in ("k", "v"):
        np.testing.assert_allclose(f32(got[name])[pages],
                                   f32(want[name])[pages],
                                   rtol=tol, atol=tol, err_msg=name)
        # the step wrote: the continuation row's pages changed
        assert not np.array_equal(f32(got[name])[pages],
                                  f32(arena[name])[pages])


def _packed_stack(stack):
    """(cfg, float32 params, arena maker) of a stack the packed step
    runs on: the tiny Llama block (one scanned layer tree, one K and one
    V pool), MiMo-V2.5's typed stack at the benchmark's rehearsal widths
    (a full and a window kind with its sink, K heads of 192 and V of 128,
    a dense layer, then a top-8-of-16 router with 4 experts held) or
    GigaChat3.1's latent stack at its rehearsal widths (one pool of one
    row a token; the engine makes its arena), or Nemotron 3 Nano's hybrid
    stack at its rehearsal widths (``ME*M``: state pools of 8 sequence
    slots beside the attention layer's pages; its steps take ``slots=``)."""
    from deepspeed_tpu.models.transformer import init_params
    if stack == "uniform":
        cfg = llama3_config("tiny")
        params = init_params(cfg, jax.random.PRNGKey(3))
        return cfg, params, lambda nb, bs: pa.init_arena(
            cfg.num_layers, cfg.kv_heads, nb, bs, cfg.head_dim, jnp.float32)
    import dataclasses
    import json
    import os
    from benchmark.lib import model as model_lib
    configs = os.path.join(os.path.dirname(model_lib.__file__), "..",
                           "configs")
    if stack == "latent":
        conf = json.load(open(os.path.join(
            configs, "gigachat3.1-l5-e16-serve.json")))
        cfg = dataclasses.replace(
            model_lib.build_model(conf, rehearse=True), init_std=0.1)
        assert cfg.typed and cfg.latent and set(cfg.layer_kinds) == {2}
        return cfg, init_params(cfg, jax.random.PRNGKey(3)), None
    if stack == "recurrent":
        from deepspeed_tpu.ops import ssm
        conf = json.load(open(os.path.join(
            configs, "nemotron3-nano-l26-e16-serve.json")))
        cfg = dataclasses.replace(
            model_lib.build_model(conf, rehearse=True), init_std=0.1)
        assert cfg.recurrent and cfg.layer_kinds == (3, -1, 0, 3)

        def make_arena(nb, bs):
            arena = pa.init_arena_typed(
                cfg.layer_kinds, {0: cfg.kv_heads}, nb, bs, cfg.head_dim,
                cfg.v_dim, jnp.float32)
            return dict(arena, **ssm.init_state_pools(cfg, 8, jnp.float32))
        return cfg, init_params(cfg, jax.random.PRNGKey(3)), make_arena
    conf = json.load(open(os.path.join(
        configs, "mimo-v2.5-l7-e16-serve.json")))
    # a window of 24: the histories below pass it, the chunks straddle it
    cfg = dataclasses.replace(model_lib.build_model(conf, rehearse=True),
                              init_std=0.1, sliding_window=24)
    assert cfg.typed and set(cfg.layer_kinds) == {0, 1} and \
        cfg.experts_held and cfg.head_dim == 192 and cfg.v_dim == 128
    params = init_params(cfg, jax.random.PRNGKey(3))
    for i, lp in enumerate(params["layers"]):
        if "moe" in lp:        # a bias that selects, as the trained one does
            lp["moe"]["router_bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 + i), lp["moe"]["router_bias"].shape)
    return cfg, params, lambda nb, bs: pa.init_arena_typed(
        cfg.layer_kinds,
        {a: cfg.kind_kv_heads(a) for a in set(cfg.layer_kinds)}, nb, bs,
        cfg.head_dim, cfg.v_dim, jnp.float32)


#: case -> (stack, fresh_prefill, tokens a row feeds, token_capacities);
#: the chunk is 16 wide, so a row of 1 is a decode row riding along
_PACKED_CASES = {
    "decode61_chunks3": ("uniform", "split", [1] * 61 + [16] * 3,
                         (128, 256)),
    "partial_chunks": ("uniform", "split", [5, 16, 9, 1, 1, 3, 16, 1],
                       (64, 96)),
    "rows_without_tokens": ("uniform", "split", [0, 16, 0, 1, 7, 0, 1, 0],
                            (32, 64)),
    "fills_the_capacity": ("uniform", "split", [16] * 6 + [3, 1], (32, 100)),
    "at_the_small_capacity": ("uniform", "split",
                              [16, 8, 1, 1, 1, 1, 3, 1], (32, 64)),
    "one_over_the_small_capacity": ("uniform", "split",
                                    [16, 8, 1, 1, 1, 1, 4, 1], (32, 64)),
    "one_capacity": ("uniform", "split", [16, 1, 1, 7, 0, 1, 2, 1], (40,)),
    "fresh": ("uniform", "fresh", [16, 5, 0, 9, 16, 1, 2, 7], (64,)),
    "paged_escape_hatch": ("uniform", False, [16, 5, 0, 9, 1, 1, 2, 7],
                           (48,)),
    "typed_small_capacity": ("typed", "split", [1, 16, 1, 0, 9, 1, 1, 1],
                             (32, 64)),
    "typed_large_capacity": ("typed", "split", [16, 16, 1, 0, 9, 1, 1, 1],
                             (32, 64)),
    "typed_fresh": ("typed", "fresh", [16, 5, 0, 9, 1, 1, 2, 7], (48,)),
}


_TAKES_THE_LARGE = {"fills_the_capacity", "one_over_the_small_capacity",
                    "typed_large_capacity"}


@pytest.mark.parametrize("case", list(_PACKED_CASES))
def test_packed_chunk_step_matches_row_form(devices, case):
    """A chunk step whose token-wise sublayers run over the batch's tokens
    packed into ``token_capacities`` slots (attention alone on rows)
    against the same step in row form (``token_capacities=()``, every
    sublayer over ``[rows, chunk]``): the logits of every row that fed a
    token and every page of the arena but each layer's trash page. Both
    sides of the capacity switch, a batch that fills its capacity to the
    last slot, rows with no token first, between and last."""
    stack, mode, counts, capacities = _PACKED_CASES[case]
    cfg, params, make_arena = _packed_stack(stack)
    rng = np.random.default_rng(len(case))
    counts = np.asarray(counts, np.int32)
    n, c, bs, mb = len(counts), 16, 8, 8
    assert sum(counts) <= capacities[-1] < n * c
    if len(capacities) == 2:       # the side of the switch the name says
        assert (sum(counts) > capacities[0]) == (case in _TAKES_THE_LARGE)
    # histories of 1..48 tokens (past the typed stack's window of 24), a
    # fresh row among them; a fresh step has none
    starts = np.zeros(n, np.int32) if mode == "fresh" else \
        rng.integers(1, 48, n).astype(np.int32) * (np.arange(n) != 1)
    pages = -(-(starts + counts) // bs)
    nb = int(pages.sum())
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))
    for i in range(n):
        pt[i, :pages[i]] = [next(free) for _ in range(pages[i])]
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = make_arena(nb, bs)
    if starts.any():        # the history, through the row-form paged read
        _, arena = ragged_forward(cfg, params, arena, toks(n, 48),
                                  jnp.asarray(starts),
                                  jnp.zeros((n,), jnp.int32),
                                  jnp.asarray(pt))
    step = (toks(n, c), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray(pt))
    want_logits, want = ragged_forward(cfg, params, arena, *step,
                                       fresh_prefill=mode)
    got_logits, got = ragged_forward(cfg, params, arena, *step,
                                     fresh_prefill=mode,
                                     token_capacities=capacities)
    live = counts > 0
    assert np.abs(np.asarray(want_logits)[live]).max() > 0.1
    np.testing.assert_allclose(np.asarray(got_logits)[live],
                               np.asarray(want_logits)[live],
                               rtol=2e-4, atol=2e-4)
    assert set(got) == set(want)
    for name in want:
        kept = np.arange(want[name].shape[0]) % (nb + 1) != nb
        a, b, before = (np.asarray(x[name])[kept]
                        for x in (got, want, arena))
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=name)
        assert not np.array_equal(a, before), name     # the step wrote


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stack", ["uniform", "typed", "latent"])
def test_packing_engine_serves_and_caches_what_a_row_form_engine_does(
        devices, stack, dtype):
    """An engine whose 4-row chunk programs pack (``max_batch_tokens`` 80
    under 4 x 96 row slots: the split program at 64 and 80 slots, the
    fresh one at 80) against one whose programs keep the row form (a
    budget of 384, held to 80 tokens a step by the caller, so both pack
    the same batches): the same greedy tokens, and every pool the same
    outside the layers' trash pages — the packed write scatters what the
    row write scatters, where it scatters it."""
    from deepspeed_tpu import telemetry
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, _ = _packed_stack(stack)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (100, 30, 70, 40)]

    def serve(max_batch_tokens):
        eng = RaggedInferenceEngineTPU(
            cfg, {"dtype": dtype, "max_sequences": 4, "num_blocks": 48,
                  "block_size": 8, "max_seq_len": 128, "prefill_chunk": 96,
                  "max_batch_tokens": max_batch_tokens}, params=params)
        before = telemetry.registry.counter("dispatch/kv_write_slots").value
        eng.scheduler.put([0, 1, 2, 3], prompts)
        tokens, slots = {uid: [] for uid in range(4)}, []
        for _ in range(10):
            out = eng.step_with_budget(budget=80)
            now = telemetry.registry.counter("dispatch/kv_write_slots").value
            slots.append((eng.last_program, now - before))
            before = now
            for uid, tok in out.items():
                tokens[uid].append(int(tok))
                eng.scheduler.put([uid], [[int(tok)]])
        return eng, tokens, slots

    packing, got, slots = serve(80)
    rows, want, row_slots = serve(384)
    assert packing._token_capacities(4, 96, "split") == (64, 80) and \
        rows._token_capacities(4, 96, "split") == ()
    # 80 of the first prompt alone (one row: one capacity); two steps of
    # 80 tokens over three and four rows (the top capacity: two blocks of
    # the small one); the last prompt's end beside decode rows (the small)
    assert slots[:5] == [("fresh", 80), ("split", 128), ("split", 128),
                         ("split", 64), ("decode", 4)], slots
    assert [s for _, s in row_slots[:5]] == [96, 384, 384, 384, 4]
    assert got == want and all(len(t) >= 6 for t in got.values())
    nb = packing.config.num_blocks
    tol = 2e-4 if dtype == "float32" else 2e-2
    assert set(packing.arena) == set(rows.arena)
    from deepspeed_tpu.inference.engine_v2 import FED_TOKENS
    for name, pool in packing.arena.items():
        # every pool but its trash pages; the slot buffer but its trash slot
        kept = np.arange(pool.shape[0]) % (nb + 1) != nb \
            if name != FED_TOKENS else np.arange(pool.shape[0]) < \
            packing.config.max_sequences
        a, b = (np.asarray(x, np.float32)[kept]
                for x in (pool, rows.arena[name]))
        assert np.abs(a).max() > 0.01, name
        # the values are the two forms' own (a matmul over [1, 80] slots
        # against one over [4, 96]): equal to their rounding
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("stack", ["uniform", "typed"])
def test_engine_serves_through_the_history_kernel_what_the_xla_reader_does(
        devices, monkeypatch, stack):
    """The engine with the paged kernels forced on (interpret mode) against
    the engine on the XLA readers, float32: the split steps' history goes
    through ``paged_attn_lse`` with each row's live-query count — prompt
    chunks of 32 and of a partial tile beside decode rows of one — and the
    greedy tokens and the pools come out the same."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, _ = _packed_stack(stack)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (100, 30, 70, 41)]
    calls = []

    def history_kernel(*args, **kw):
        calls.append(kw.get("qcounts") is not None)
        return kernel(*args, interpret=True, **kw)

    kernel = pa.paged_attention_with_lse
    monkeypatch.setattr(pa, "paged_attention_with_lse", history_kernel)
    monkeypatch.setattr(pa, "paged_attention", functools.partial(
        pa.paged_attention, interpret=True))

    def serve(use_pallas):
        eng = RaggedInferenceEngineTPU(
            cfg, {"dtype": "float32", "max_sequences": 4, "num_blocks": 48,
                  "block_size": 8, "max_seq_len": 128, "prefill_chunk": 32,
                  "max_batch_tokens": 80, "use_pallas": use_pallas},
            params=params)
        eng.scheduler.put([0, 1, 2, 3], prompts)
        tokens, programs = {uid: [] for uid in range(4)}, []
        for _ in range(9):
            out = eng.step_with_budget(budget=80)
            programs.append(eng.last_program)
            for uid, tok in out.items():
                tokens[uid].append(int(tok))
                eng.scheduler.put([uid], [[int(tok)]])
        return eng, tokens, programs

    from deepspeed_tpu import telemetry
    tiles = lambda: [telemetry.registry.counter("dispatch/" + name).value
                     for name in ("query_tiles", "query_tiles_live",
                                  "kv_pages_walked", "kv_page_fetches")]
    before = tiles()
    xla, want, _ = serve(False)
    assert not calls and tiles() == before     # the XLA reader: no tiles
    got_eng, got, programs = serve(True)
    assert calls and all(calls) and programs.count("split") >= 3
    held, computed, walked, fetches = (
        now - was for now, was in zip(tiles(), before))
    # at these widths every call holds all of a row's KV heads: a page is
    # one DMA of K and one of V
    assert 0 < walked and fetches == 2 * walked
    # rows of one live query rode beside the chunks: one tile each
    assert 0 < computed < held and held % (32 // pa.tile_queries(
        32, cfg.num_heads // cfg.kv_heads)) == 0
    assert got == want and all(len(t) >= 4 for t in got.values())
    nb = xla.config.num_blocks
    for name, pool in got_eng.arena.items():
        kept = np.arange(pool.shape[0]) % (nb + 1) != nb
        a, b = np.asarray(pool)[kept], np.asarray(xla.arena[name])[kept]
        if a.shape != b.shape:      # the kernel's K pool: whole lane tiles
            heads = cfg.kind_kv_heads(1 if name.endswith("_win") else 0)
            a = a.reshape(*a.shape[:2], heads, -1)[..., :cfg.head_dim] \
                .reshape(b.shape)
        assert np.abs(a).max() > 0.01, name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


#: case -> (the benchmark configuration whose rehearsal stack it runs, what
#: replaces of its ``DecoderConfig``, the layer kinds it must hold)
_TYPED_DECODE = {
    # a full kind and a window kind with its learned sink, K heads of 192
    # in pools of 256 lanes (``_paged_reader``'s whole tiles) beside V's 128
    "full_and_window_with_a_sink": (
        "mimo-v2.5-l7-e16-serve", {"sliding_window": 24}, (0, 1, 1)),
    # no sink, three window layers before the full one, the parallel block
    "window_without_a_sink": (
        "command-a-plus-l4-e16-serve", {"sliding_window": 24}, (1, 1, 1, 0)),
    # heads of 64: the kernel reads two KV heads as one of 128 lanes, the
    # pools unpadded; short convolutions around the attention layer
    "paired_heads_of_64": (
        "lfm2-24b-a2b-l40-e8-serve", {}, (5, 5, 0, 5)),
    # one full layer between state-space layers
    "full_between_state_spaces": (
        "nemotron3-nano-l26-e16-serve", {}, (3, -1, 0, 3)),
}


@pytest.mark.parametrize("case", list(_TYPED_DECODE))
def test_typed_decode_step_reads_through_the_kernel_what_the_xla_read_does(
        monkeypatch, case):
    """A typed stack's DECODE program (``c == 1``: it reads what it has
    just written) with the paged kernel in it (``use_pallas``; interpret
    mode) against the same program on ``paged_attention_xla``, float32:
    the kernel is called once an attention layer under the name
    ``paged_attn_decode`` with the step's ``counts``, and the live rows'
    logits and every pool come out the same — a row whose own key is all
    it sees (no page of history), one inside its first page, one whose key
    opens a new page, one many pages long and past the window, and a
    padding row (no token, the trash page)."""
    import dataclasses
    from benchmark.lib import model as model_lib
    from deepspeed_tpu.inference import engine_v2
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.ops import ssm
    name, replaced, kinds = _TYPED_DECODE[case]
    # five rows of tiny pages gather a few KB: every program takes the kernel
    monkeypatch.setattr(pa, "DECODE_KERNEL_BYTES", 0)
    cfg = dataclasses.replace(
        model_lib.build_model(model_lib.load_config(name), rehearse=True),
        init_std=0.1, **replaced)
    assert cfg.layer_kinds == kinds
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(len(case))
    bs, mb, hist = 8, 8, 48
    starts = np.asarray([0, 5, 16, 43, 0], np.int32)
    counts = np.asarray([1, 1, 1, 1, 0], np.int32)
    n, live = len(starts), counts > 0
    pages = np.where(live, -(-(starts + counts) // bs), 0)
    nb = int(pages.sum())
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))
    for i in range(n):
        pt[i, :pages[i]] = [next(free) for _ in range(pages[i])]
    slots = jnp.asarray(np.where(live, np.arange(n), 8), jnp.int32)
    history = jnp.asarray(rng.integers(0, cfg.vocab_size, (n, hist)),
                          jnp.int32)
    step = jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 1)), jnp.int32)
    calls = []

    def decode_kernel(*args, **kw):
        calls.append((kw.get("name"), args[0].shape[1],
                      args[5] is kw.get("qcounts")))
        return kernel(*args, interpret=True, **kw)

    kernel = pa.paged_attention_with_lse
    monkeypatch.setattr(pa, "paged_attention_with_lse", decode_kernel)

    def run(use_pallas):
        _, lanes = engine_v2._paged_reader(cfg, types.SimpleNamespace(
            use_pallas=use_pallas, block_size=bs))
        arena = pa.init_arena_typed(
            cfg.layer_kinds,
            {a: cfg.kind_kv_heads(a) for a in set(kinds) & set(pa.KIND_POOLS)},
            nb, bs, lanes, cfg.v_dim, jnp.float32)
        if cfg.recurrent:
            arena.update(ssm.init_state_pools(cfg, 8, jnp.float32))
        kw = {"use_pallas": use_pallas,
              "slots": slots if cfg.recurrent else None}
        # the history, through the paged mode's chunk (the XLA read)
        _, arena = engine_v2.ragged_forward(
            cfg, params, arena, history, jnp.asarray(starts),
            jnp.zeros((n,), jnp.int32), jnp.asarray(pt), **kw)
        assert not calls
        return engine_v2.ragged_forward(
            cfg, params, arena, step, jnp.asarray(counts),
            jnp.asarray(starts), jnp.asarray(pt), **kw), lanes

    (want_logits, want), width = run(False)
    assert not calls
    (got_logits, got), lanes = run(True)
    layers = sum(1 for kind in kinds if kind in (0, 1))
    assert calls == [(pa.DECODE_KERNEL, 1, True)] * layers
    # ... and as the code has it, a program whose rows gather this little
    # keeps the XLA read (``pa.decode_reads_by_kernel``)
    monkeypatch.undo()
    del calls[:]
    run(True)
    assert not calls
    assert lanes % 128 == 0 or pa.pairs_heads(
        cfg.head_dim, cfg.v_dim, cfg.kind_kv_heads(0))
    assert np.abs(np.asarray(want_logits)[live]).max() > 0.1
    np.testing.assert_allclose(np.asarray(got_logits)[live],
                               np.asarray(want_logits)[live],
                               rtol=2e-4, atol=2e-4)
    assert set(got) == set(want)
    for pool in want:
        a, b = np.asarray(got[pool]), np.asarray(want[pool])
        if not ssm.is_state_pool(pool):
            kept = np.arange(b.shape[0]) % (nb + 1) != nb
            a, b = a[kept], b[kept]
        else:
            a, b = a[:-1], b[:-1]
        if a.shape != b.shape:      # the kernel's K pool: whole lane tiles
            heads = cfg.kind_kv_heads(1 if pool.endswith("_win") else 0)
            a = a.reshape(*a.shape[:2], heads, lanes)[..., :width] \
                .reshape(b.shape)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=pool)


def _page_work_site(stack, use_pallas=True):
    """What ``launch_work.kv_page_work`` reads of a site, at the serving
    cells' widths over pages of 128: Mistral-7B's 12 layers of 32 / 8 heads
    of 128; MiMo-V2.5's two window layers (64 / 8 heads, a window of 128)
    and one full layer (4 KV heads), K 256 and V 128 lanes a head; a latent
    stack."""
    import types
    from deepspeed_tpu.inference import launch_work
    typed = stack != "uniform"
    model = types.SimpleNamespace(
        latent=stack == "latent", typed=typed, num_layers=12,
        layer_kinds=(1, 1, 0) if typed else None,
        num_heads=64 if typed else 32, v_dim=128,
        kind_kv_heads=lambda kind: (8 if kind == 1 else 4) if typed else 8,
        kind_window=lambda kind: 128 if kind == 1 else None,
        picks_keys=False, recurrent=False, num_experts=0, hc_mult=1)
    return launch_work.Site(model, 128, 32, use_pallas,
                            k_lanes=256 if typed else 128, itemsize=2)


#: case → (stack, chunk, grouped, rows' starts, rows' fed tokens, want)
_PAGE_WORK = {
    # 12 layers x (2 + 1 + 2) pages to each row's own key, all 8 heads a
    # fetch of K and one of V
    "decode": ("uniform", 1, False, (200, 127, 128), (1, 1, 1), (60, 120)),
    # histories of 3 and 2 pages (a fresh row and a padded row read none):
    # the one-token row's call holds 8 heads a program, the chunk row's 1
    "split_grouped": ("uniform", 128, True, (300, 0, 256, 128),
                      (1, 100, 128, 0), (60, 12 * 2 * (3 * 1 + 2 * 8))),
    # ... and in the row form every row's block is the chunk's
    "split_rows": ("uniform", 128, False, (300, 0, 256, 128),
                   (1, 100, 128, 0), (60, 12 * 2 * (3 * 8 + 2 * 8))),
    # ... of which a chunk of 32 holds 4 heads a program (128 matmul rows)
    "split_rows_chunk_32": ("uniform", 32, False, (300, 0, 256, 128),
                            (1, 30, 32, 0), (60, 12 * 2 * (3 * 2 + 2 * 2))),
    # a window layer walks from its window's first page (pages 4 and 5 of
    # 700 tokens), the full layer all 6; the chunk row's blocks of 1,024
    # and 2,048 matmul rows take a head a program
    "typed_split_grouped": ("typed", 128, True, (700, 100), (1, 128),
                            (2 * 3 + 7, 2 * 2 * (2 * 1 + 1 * 8) +
                             2 * (6 * 1 + 1 * 4))),
    # a typed stack's decode program reads through the kernel too: each of
    # two window layers its window's pages to the row's own key (700: pages
    # 4 and 5; 127: page 0; 128: its own key opens page 1, the window's
    # first key lies in page 0), the full layer 6 + 1 + 2; a row of one
    # query holds every KV head a program; a padded row reads nothing
    "typed_decode": ("typed", 1, False, (700, 127, 128, 0), (1, 1, 1, 0),
                     (2 * 5 + 9, 2 * (2 * 5 + 9))),
    # ... and from the window's first page only: a row far past it
    "typed_decode_window_walks_two_pages": (
        "typed", 1, False, (3000,), (1,), (2 * 2 + 24, 2 * (2 * 2 + 24))),
    # ... in the 64-row program. An 8-row program's window layers would
    # gather 8 rows x 2 pages x 786 KB = 12.6 MB, under
    # ``pa.DECODE_KERNEL_BYTES``: they keep the XLA read and count nothing;
    # its full layer (8 x 32 pages x 393 KB = 100.7 MB) walks its pages
    "typed_decode_of_8_rows": ("typed", 1, False, (3000,), (1,), (24, 48)),
    "latent_is_another_kernel": ("latent", 128, True, (700,), (1,), None),
}
#: a launch's token slots (a decode launch's: its program's rows) where not 64
_PAGE_WORK_SLOTS = {"typed_decode_of_8_rows": 8}


@pytest.mark.parametrize("case", list(_PAGE_WORK))
def test_page_fetches_follow_the_heads_a_program_holds(case):
    """``dispatch/kv_pages_walked`` / ``dispatch/kv_page_fetches`` of a
    launch (``launch_work.kv_page_work``, host arithmetic): the live pages
    the paged kernel's readers must read over all attention layers, and
    two DMAs a page and program — ``kv_heads / heads_per_program`` programs
    a row, by the block its call gives it. Without the kernel: nothing."""
    from deepspeed_tpu.inference import launch_work
    stack, chunk, grouped, starts, fed, want = _PAGE_WORK[case]
    launch = launch_work.Launch(
        "split" if chunk > 1 else "decode", chunk, grouped,
        _PAGE_WORK_SLOTS.get(case, 64), sum(fed), np.asarray(starts),
        np.asarray(fed))
    site = _page_work_site(stack)
    reads = lambda at: launch_work.kv_page_work in [t.work for t in at.terms]
    assert reads(site) == (stack != "latent")
    got = launch_work.kv_page_work(site, launch) if reads(site) else {}
    assert (tuple(got.values()) or None) == want
    assert list(got) == ["kv_pages_walked", "kv_page_fetches"][:len(got)]
    assert not reads(_page_work_site(stack, use_pallas=False))


def test_capacities_past_the_rows_slots_are_refused(devices):
    """No ``token_capacities`` passes the rows' slots (AT them a split
    program's top instance packs with every row a chunk row), and only a
    split step switches between two."""
    cfg = llama3_config("tiny")
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    arena = pa.init_arena(cfg.num_layers, cfg.kv_heads, 4, 8, cfg.head_dim,
                          jnp.float32)
    z = jnp.zeros((2,), jnp.int32)
    args = (cfg, params, arena, jnp.zeros((2, 16), jnp.int32), z, z,
            jnp.full((2, 4), 4, jnp.int32))
    for mode, capacities in (("split", (33,)), ("split", (8, 40)),
                             ("fresh", (8, 16)), (False, (8, 16))):
        with pytest.raises(ValueError, match="token_capacities"):
            ragged_forward(*args, fresh_prefill=mode,
                           token_capacities=capacities)


def test_a_batch_over_the_token_capacity_is_refused_by_name(devices):
    """A 8-row x 8-wide step of this engine packs its tokens into
    ``max_batch_tokens`` = 32 slots; the scheduler's own budget never
    passes that, a caller's ``budget=`` can: refused before any launch,
    and nothing of the batch consumed."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    eng = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 64, "prefill_chunk": 8, "max_batch_tokens": 32,
              "max_sequences": 8})
    assert eng._token_capacities(8, 8, "split") == (32,)
    assert eng._token_capacities(4, 8, "split") == ()      # 32 slots: rows
    assert eng._token_capacities(8, 1, False) == ()        # decode: rows
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=(8,), dtype=np.int32)
               for _ in range(8)]
    eng.scheduler.put(list(range(8)), prompts)
    with pytest.raises(ValueError, match="max_batch_tokens=32"):
        eng.step_with_budget(budget=64)
    assert all(seq.seen_tokens == 0 for seq in eng.state.seqs.values())
    # under the engine's own budget the same queue drains, packed
    while eng.step_with_budget() is not None:
        pass
    assert all(seq.pending == 0 for seq in eng.state.seqs.values())


def test_flash_attention_with_lse_matches_xla(devices):
    from deepspeed_tpu.ops.flash_attention import flash_attention_with_lse
    from deepspeed_tpu.ops.paged_attention import causal_attention_with_lse
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    o1, l1 = flash_attention_with_lse(q, k, v, interpret=True)
    o2, l2 = causal_attention_with_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-5, atol=2e-5)


def test_chunked_retirement_per_seq_budgets(devices):
    """Per-sequence max_new_tokens with chunk-boundary retirement must
    produce token-for-token the same output as solo dense generation —
    across MULTIPLE fused chunks (budgets straddle the 32-step chunk
    bucket) and with retired rows leaving the batch mid-generation."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(3))

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, size=(n,), dtype=np.int32)
               for n in (5, 11, 23, 17)]
    budgets = [3, 40, 70, 33]     # straddle chunk boundaries + early out

    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 96, "block_size": 16,
              "max_seq_len": 128, "prefill_chunk": 8,
              "max_batch_tokens": 64},
        params=params)
    outs = v2.generate(prompts, max_new_tokens=budgets)

    v1 = init_inference(cfg, {"dtype": "float32"}, params=params)
    for p, m, got in zip(prompts, budgets, outs):
        assert len(got) == len(p) + m
        ref = v1.generate(p[None, :], max_new_tokens=m)[0]
        np.testing.assert_array_equal(got, ref[:len(p) + m])

    # all pages released after generate
    assert len(v2.state.seqs) == 0

    # one token a step agrees too (no window armed)
    outs2 = _generate_stepwise(v2, prompts, budgets)
    assert v2.state.allocator.free_blocks == 96
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)


def test_generate_refuses_oversized_before_compute(devices):
    """Oversized requests fail BEFORE any compute."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 64, "prefill_chunk": 8,
              "max_batch_tokens": 64})
    rng = np.random.default_rng(1)
    big = rng.integers(0, 256, size=(40,), dtype=np.int32)
    with pytest.raises(ValueError, match="over max_seq_len"):
        v2.generate([big], max_new_tokens=40)
    assert len(v2.state.seqs) == 0 and not v2._step_fns
    # generate() keeps only its own rows' tokens, so it refuses to step
    # a streaming caller's queued tokens away (and leaves them queued)
    v2.scheduler.put([5], [[1, 2, 3]])
    with pytest.raises(RuntimeError, match="streaming"):
        v2.generate([[4, 5]], max_new_tokens=2)
    assert v2.state.seqs[5].pending == 3 and not v2._step_fns
    v2.step_with_budget()
    assert len(v2.generate([[4, 5]], max_new_tokens=2)[0]) == 4
    assert list(v2.state.seqs) == [5]


#: the greedy tokens of :func:`test_golden_greedy_tokens`'s flow, a row a
#: line (first token after the chunked prefill, nine from decode steps —
#: the last six from one fused window when recorded), RECORDED FROM THE
#: PARENT OF PR 34 (head-major
#: pools) before the arena went token-major: a layout moves no token
_GOLDEN_GREEDY = [
    [153, 69, 181, 12, 181, 12, 69, 236, 86, 229],
    [132, 53, 66, 89, 195, 184, 126, 31, 133, 171],
    [213, 143, 101, 129, 119, 102, 6, 169, 101, 129],
    [40, 233, 21, 122, 39, 23, 228, 154, 178, 172],
]


def test_golden_greedy_tokens(devices):
    """A small uniform-stack engine over a fixed ragged batch — prompts of
    one to four prefill chunks (a ``fresh`` step, then ``split`` steps
    that mix rows with and without history), then nine single-token
    decode steps (the arena in the layer scan's carry, write then read) —
    emits the tokens it emitted before PR 34 changed the arena's layout."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    eng = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 32, "block_size": 16,
              "max_seq_len": 96, "prefill_chunk": 8, "max_batch_tokens": 64},
        params=init_params(cfg, jax.random.PRNGKey(34)))
    rng = np.random.default_rng(34)
    uids = [0, 1, 2, 3]
    got = {u: [] for u in uids}
    eng.scheduler.put(uids, [[int(t) for t in rng.integers(0, 256, size=n)]
                             for n in (5, 11, 23, 30)])
    programs = []

    def drain():
        while (out := eng.step_with_budget()) is not None:
            programs.append(eng.last_program)
            for u, tok in out.items():
                got[u].append(tok)

    drain()                                     # the chunked prefill
    for _ in range(9):                          # decode, one token a step
        eng.scheduler.put(uids, [got[u][-1:] for u in uids])
        drain()
    assert programs == ["fresh"] + ["split"] * 3 + ["decode"] * 9
    assert [got[u] for u in uids] == _GOLDEN_GREEDY


# -- one layout: pools [pages, block_size, kv_heads * head_dim] (PR 34) ----

def _dense_attention(q, k, v, starts, counts):
    """Row i's queries (positions ``starts[i] + j``, j < counts[i]) over
    its own contiguous keys ``k[i, :starts[i] + counts[i]]``, causally, one
    (row, head) at a time in float64 numpy: out [n, c, h, dv] and the
    logsumexp [n, c, h]. Rows past ``counts`` stay zero."""
    n, c, h, dk = q.shape
    kvh = k.shape[2]
    out = np.zeros((n, c, h, v.shape[-1]))
    lse = np.zeros((n, c, h))
    for i in range(n):
        for j in range(counts[i]):
            visible = starts[i] + j + 1
            for head in range(h):
                kh = head // (h // kvh)
                s = k[i, :visible, kh].astype(np.float64) @ \
                    q[i, j, head].astype(np.float64) / np.sqrt(dk)
                p = np.exp(s - s.max())
                out[i, j, head] = p @ v[i, :visible, kh] / p.sum()
                lse[i, j, head] = s.max() + np.log(p.sum())
    return out, lse


#: reader case -> (chunk width, each row's new tokens): the decode
#: program's reader, the same kernel over a chunk, the lse form
_READER_CASES = {"decode": (1, [1, 1, 1, 0]), "chunk": (8, [8, 3, 8, 0]),
                 "chunk_lse": (8, [8, 3, 8, 0])}


@pytest.mark.parametrize("case", list(_READER_CASES))
def test_paged_readers_match_a_dense_reference(case):
    """``write_kv`` into a two-layer pool through an out-of-order page
    table, then the Pallas readers (interpret mode: ``paged_attention`` at
    c = 1 and c > 1, ``paged_attention_with_lse``) and the XLA reader over
    the second layer's region, against plain attention over each row's
    contiguous keys. Heads of 128 side by side on the lanes; a padded row
    among the live ones."""
    c, counts = _READER_CASES[case]
    rng = np.random.default_rng(34)
    kvh, h, dh, bs, nb, mb, n = 2, 4, 128, 16, 9, 3, 4
    starts = np.asarray([5, 30, 17, 0], np.int32)
    counts = np.asarray(counts, np.int32)
    arena = pa.init_arena(2, kvh, nb, bs, dh, jnp.float32)
    assert arena["k"].shape == (2 * (nb + 1), bs, kvh * dh)
    off = int(pa.layer_page_offset(1, nb))
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))
    for i in range(3):
        pages = -(-(starts[i] + counts[i]) // bs)
        pt[i, :pages] = [next(free) for _ in range(pages)]
    pt_l = jnp.asarray(pt + off)
    k = rng.standard_normal((n, mb * bs, kvh, dh)).astype(np.float32)
    v = rng.standard_normal((n, mb * bs, kvh, dh)).astype(np.float32)
    # the history, then the step's own tokens: two writes, as the engine's
    ak, av = _write_chunk(arena["k"], arena["v"], jnp.asarray(k),
                         jnp.asarray(v), pt_l, jnp.zeros((n,), jnp.int32),
                         jnp.asarray(starts), trash_block=off + nb)
    new = np.stack([np.stack([x[i, starts[i]:starts[i] + c] for i in range(n)])
                    for x in (k, v)])
    ak, av = _write_chunk(ak, av, jnp.asarray(new[0]), jnp.asarray(new[1]),
                         pt_l, jnp.asarray(starts), jnp.asarray(counts),
                         trash_block=off + nb)
    assert not np.asarray(ak)[:off].any()             # layer 0 untouched
    q = rng.standard_normal((n, c, h, dh)).astype(np.float32)
    want, want_lse = _dense_attention(q, k, v, starts, counts)
    args = (jnp.asarray(q), ak, av, pt_l, jnp.asarray(starts),
            jnp.asarray(counts))
    xla, xla_lse = pa.paged_attention_xla(*args, with_lse=True)
    if case == "chunk_lse":
        got, got_lse = pa.paged_attention_with_lse(*args, interpret=True)
    else:
        got, got_lse = pa.paged_attention(*args, interpret=True), None
    live = np.arange(c)[None] < counts[:, None]
    assert live.any(axis=1).tolist() == [True, True, True, False]
    for name, out, lse in (("xla", xla, xla_lse), ("pallas", got, got_lse)):
        np.testing.assert_allclose(np.asarray(out)[live], want[live],
                                   rtol=2e-5, atol=2e-5, err_msg=name)
        if lse is not None:
            np.testing.assert_allclose(np.asarray(lse)[live], want_lse[live],
                                       rtol=2e-5, atol=2e-5, err_msg=name)


def test_write_then_gather_round_trip_and_the_layers_trash_page():
    """What ``write_kv`` scatters, ``_gather_pages`` reads back head by
    head; a row's padded tokens and a padded row land in the trash page
    the caller names (the LAYER's, not the pool's last) and nowhere
    else."""
    rng = np.random.default_rng(3)
    kvh, dh, bs, nb, n, c = 2, 16, 4, 5, 3, 6
    arena = pa.init_arena(3, kvh, nb, bs, dh, jnp.float32)
    off, trash = nb + 1, 2 * nb + 1               # layer 1 of 3
    pt = jnp.asarray([[2, 4], [0, 1], [nb, nb]], jnp.int32) + off
    k = rng.standard_normal((n, c, kvh, dh)).astype(np.float32)
    v = rng.standard_normal((n, c, kvh, dh)).astype(np.float32)
    counts = np.asarray([6, 2, 0], np.int32)
    ak, av = _write_chunk(arena["k"], arena["v"], jnp.asarray(k),
                         jnp.asarray(v), pt, jnp.zeros((n,), jnp.int32),
                         jnp.asarray(counts), trash_block=trash)
    for pool, new in ((ak, k), (av, v)):
        back = np.asarray(pa._gather_pages(pool, pt, kvh))  # [n,S,kvh,dh]
        for i in range(n - 1):
            np.testing.assert_array_equal(back[i, :counts[i]],
                                          new[i, :counts[i]])
            assert not back[i, counts[i]:].any()
        pool = np.asarray(pool)
        written = {int(p) for p in np.flatnonzero(pool.any(axis=(1, 2)))}
        assert written == {off + 2, off + 4, off + 0, trash}


#: how a step holds its tokens -> ``_TokenLayout``'s capacity: rows as
#: they arrive, or packed into the small / the top capacity of a program
_SLOT_FORMS = {"rows": None, "small_capacity": 40, "top_capacity": 64}
#: batch -> (tokens a row feeds, tokens it has cached), pages of 8 under a
#: chunk of 16: decode rows over a history, rows with no token, a chunk
#: that straddles a page boundary and ends exactly on the next (5 + 11), one
#: that straddles another (13 + 7), a fresh one, a full chunk of two pages
_SLOT_BATCHES = {
    "ends_in_a_full_chunk": ([1, 0, 11, 1, 7, 0, 3, 16],
                             [9, 0, 5, 23, 13, 0, 0, 8]),
    "ends_in_rows_without_tokens": ([0, 16, 11, 1, 7, 3, 1, 0, 0],
                                    [0, 8, 5, 23, 13, 0, 9, 0, 0]),
}


def _slot_write(writer, form, batch, mask=True):
    """(pools written through ``writer`` with the layout's slots, the same
    pools written token by token in numpy, the trash page): layer 1 of a
    two-layer pool, pages out of order, pools that hold something
    already; a packed buffer's slots past the batch's tokens hold values
    of their own. ``mask=False``: every slot claims a token."""
    from deepspeed_tpu.inference.engine_v2 import _TokenLayout
    counts, starts = (np.asarray(a, np.int32) for a in _SLOT_BATCHES[batch])
    rng = np.random.default_rng(11)
    n, c, bs, mb, nb = len(counts), 16, 8, 4, 20
    off, trash = nb + 1, 2 * nb + 1
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))
    for i in range(n):
        pages = -(-(starts[i] + counts[i]) // bs) if counts[i] else 0
        pt[i, :pages] = [next(free) for _ in range(pages)]
    pt += off
    # write_kv: two pools of two heads of 8; write_rows: rows of 20 values
    # into a pool 24 lanes wide
    shapes, lanes = (((2, 8), (2, 8)), (16, 16)) if writer == "write_kv" \
        else (((20,),), (24,))
    pools = [rng.standard_normal((2 * (nb + 1), bs, w)).astype(np.float32)
             for w in lanes]
    rows = [rng.standard_normal((n, c) + sh).astype(np.float32)
            for sh in shapes]
    want = [p.copy() for p in pools]
    for pool, new in zip(want, rows):
        for i in range(n):
            for j in range(counts[i]):
                pos = starts[i] + j
                row = new[i, j].reshape(-1)
                pool[pt[i, pos // bs], pos % bs] = np.pad(
                    row, (0, pool.shape[-1] - row.size))
    capacity = _SLOT_FORMS[form]
    if capacity is None:
        held = rows
    else:       # row after row, then slots that hold no token
        held = [np.concatenate(
            [new[i, :counts[i]] for i in range(n)] +
            [rng.standard_normal((capacity - counts.sum(),) + new.shape[2:])
             .astype(np.float32)])[None] for new in rows]
    row, pos, valid = _TokenLayout(jnp.asarray(counts), jnp.asarray(starts),
                                   c, capacity).kv_slots()
    assert row.shape == (capacity or n * c,) and \
        int(valid.sum()) == counts.sum()
    if not mask:
        valid = jnp.ones_like(valid)
    write = getattr(pa, writer)
    got = write(*map(jnp.asarray, pools), *map(jnp.asarray, held),
                jnp.asarray(pt), row, pos, valid, trash_block=trash)
    got = got if isinstance(got, tuple) else (got,)
    return [np.asarray(g) for g in got], want, trash


@pytest.mark.parametrize("batch", list(_SLOT_BATCHES))
@pytest.mark.parametrize("form", list(_SLOT_FORMS))
@pytest.mark.parametrize("writer", ["write_kv", "write_rows"])
def test_token_slot_write_matches_a_token_by_token_write(writer, form, batch):
    """``write_kv`` / ``write_rows`` with ONE update a slot of the
    token-wise form — rows ``[n, c]``, or packed at either capacity —
    leave every pool, outside the layer's trash page, as a token-by-token
    write leaves it: each token's values at its page and offset (the lanes
    past a narrower row zero), nothing else moved. So the packed write
    equals the row write, pool for pool."""
    got, want, trash = _slot_write(writer, form, batch)
    for g, w in zip(got, want):
        keep = np.arange(g.shape[0]) != trash
        np.testing.assert_array_equal(g[keep], w[keep])


@pytest.mark.parametrize("writer", ["write_kv", "write_rows"])
def test_an_unmasked_slot_past_the_tokens_overwrites_a_live_token(writer):
    """The control of the comparison above: a packed slot past the batch's
    tokens carries the clipped row / column of the LAST live token, so
    with ``valid`` ignored its update lands on that token's KV — routing
    by ``valid`` is what keeps it off."""
    got, want, trash = _slot_write(writer, "top_capacity",
                                   "ends_in_a_full_chunk", mask=False)
    keep = np.arange(got[0].shape[0]) != trash
    assert all(not np.array_equal(g[keep], w[keep])
               for g, w in zip(got, want))


def test_copy_pages_copies_a_page_in_every_layer_of_every_pool():
    """``copy_pages`` over pools of different layer counts and widths (a
    typed arena's): page ``src`` of each layer's region lands on ``dst``,
    nothing else moves."""
    rng = np.random.default_rng(5)
    nb, bs = 4, 2
    arena = {"k": rng.standard_normal((3 * (nb + 1), bs, 8)),
             "v_win": rng.standard_normal((2 * (nb + 1), bs, 4))}
    arena = {name: jnp.asarray(a, jnp.float32) for name, a in arena.items()}
    got = pa.copy_pages(arena, jnp.asarray([1, 3]), jnp.asarray([0, 2]),
                        stride=nb + 1)
    for name, pool in arena.items():
        want = np.array(pool)
        for layer in range(pool.shape[0] // (nb + 1)):
            base = layer * (nb + 1)
            want[base + 0], want[base + 2] = want[base + 1], want[base + 3]
        np.testing.assert_array_equal(np.asarray(got[name]), want)


def test_exported_pages_continue_in_a_second_engine(devices):
    """``export_pages`` after a chunked prefill, ``import_pages`` at other
    page ids of a second engine with the same weights, and a sequence
    adopted over them decodes the tokens the first engine goes on to
    decode. A bundle that does not fit — another page count, the
    five-axis ``[kvh, L, m, bs, dh]`` bundle of the head-major arena — is
    refused by name."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=128, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(8))
    eng_cfg = {"dtype": "float32", "num_blocks": 16, "block_size": 8,
               "max_seq_len": 64, "prefill_chunk": 8, "max_batch_tokens": 32}
    prompt = [int(t) for t in
              np.random.default_rng(9).integers(0, 256, size=21)]

    def decode(eng, uid, first, steps):
        toks = [first]
        for _ in range(steps):
            eng.scheduler.put([uid], [toks[-1:]])
            toks.append(eng.step_with_budget()[uid])
        return toks

    src = RaggedInferenceEngineTPU(cfg, eng_cfg, params=params)
    src.scheduler.put([0], [prompt])
    while (out := src.step_with_budget()) is not None:
        first = out.get(0)
    blocks = list(src.state.seqs[0].blocks)
    assert len(blocks) == 3 and first is not None
    pages = src.export_pages(blocks)
    L, kvh, dh = cfg.num_layers, cfg.kv_heads, cfg.head_dim
    assert pages["k"].shape == pages["v"].shape == (L, 3, 8, kvh * dh)
    assert pages["k"].nbytes + pages["v"].nbytes == 3 * src.kv_page_nbytes()
    want = decode(src, 0, first, 4)

    dst = RaggedInferenceEngineTPU(cfg, eng_cfg, params=params)
    alloc = dst.state.allocator
    held = alloc.allocate(5)                    # other page ids than src's
    there = alloc.allocate(3)
    assert there != blocks
    dst.import_pages(pages, there)
    dst.state.adopt(7, prompt, there, len(prompt))
    assert decode(dst, 7, first, 4) == want
    alloc.free(held)

    for bad in ({key: a[:, :2] for key, a in pages.items()},
                {key: a.reshape(L, 3, 8, kvh, dh).transpose(3, 0, 1, 2, 4)
                 for key, a in pages.items()}):
        with pytest.raises(ValueError, match="does not fit this arena"):
            dst.import_pages(bad, there)
