"""Serving frontend tests: refcounted allocator, radix prefix cache,
SplitFuse token-budget policy, admission/backpressure/deadlines,
streaming, and the prefix-hit == cold-prefill logits parity guarantee.

All deterministic under JAX_PLATFORMS=cpu (conftest forces it)."""

import numpy as np
import pytest
import jax

from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
from deepspeed_tpu.inference.ragged import (BlockedAllocator, DSStateManager,
                                            RaggedScheduler)
from deepspeed_tpu.models.llama import llama3_config
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.serving import (AdmissionError, AdmissionQueue, Histogram,
                                   PrefixCache, Request, RequestState,
                                   ServingFrontend, ServingMetrics,
                                   TokenBudgetPolicy, adopt_cached)

ENG_CFG = {"dtype": "float32", "num_blocks": 32, "block_size": 8,
           "max_seq_len": 128, "prefill_chunk": 8, "max_batch_tokens": 64,
           "max_sequences": 16}


def _engine(devices, params_key=0, **over):
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(params_key))
    return RaggedInferenceEngineTPU(cfg, {**ENG_CFG, **over}, params=params)


# ---------------------------------------------------------------------------
# refcounted BlockedAllocator
# ---------------------------------------------------------------------------

def test_allocator_refcount_lifecycle():
    a = BlockedAllocator(4, 8)
    blocks = a.allocate(2)
    assert a.free_blocks == 2
    assert all(a.refcount(b) == 1 for b in blocks)
    a.incref(blocks)                       # second owner (e.g. the cache)
    assert all(a.refcount(b) == 2 for b in blocks)
    assert a.free(blocks) == 0             # first owner lets go: still live
    assert a.free_blocks == 2
    assert a.free(blocks) == 2             # last owner: pages return
    assert a.free_blocks == 4


def test_allocator_double_free_raises():
    a = BlockedAllocator(4, 8)
    blocks = a.allocate(1)
    a.free(blocks)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(blocks)
    with pytest.raises(RuntimeError, match="not live"):
        a.incref(blocks)
    with pytest.raises(ValueError, match="bad block"):
        a.free([99])


def test_allocator_exhaustion_raises_and_preserves_state():
    a = BlockedAllocator(4, 8)
    a.allocate(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.allocate(2)
    assert a.free_blocks == 1              # failed allocate took nothing


def test_adopt_transfers_refs_to_sequence():
    st = DSStateManager(max_sequences=4, num_blocks=8, block_size=4)
    shared = st.allocator.allocate(2)      # e.g. handed out by a cache
    st.adopt(7, list(range(11)), shared, seen_tokens=8)
    seq = st.seqs[7]
    assert seq.blocks[:2] == shared and len(seq.blocks) == 3
    assert seq.pending == 3
    st.flush(7)                            # releases adopted + tail pages
    assert st.allocator.free_blocks == 8


def test_adopt_exhaustion_rolls_back():
    st = DSStateManager(max_sequences=4, num_blocks=2, block_size=4)
    shared = st.allocator.allocate(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        st.adopt(1, list(range(12)), shared, seen_tokens=4)  # needs 2 more
    assert 1 not in st.seqs
    assert st.allocator.free_blocks == 2   # handed-over ref released too


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_match_insert_partial():
    a = BlockedAllocator(16, 4)
    cache = PrefixCache(a)
    toks = list(range(10))                 # 2 full pages + partial of 2
    blocks = a.allocate(3)
    assert cache.insert(toks, blocks) == 3
    assert all(a.refcount(b) == 2 for b in blocks)

    m = cache.match(toks)
    assert m.full_blocks == blocks[:2]
    assert m.partial_block == blocks[2] and m.partial_len == 2
    assert m.matched(4) == 10

    # diverging suffix: only the shared full pages match
    m2 = cache.match(toks[:8] + [99, 98, 97])
    assert m2.full_blocks == blocks[:2] and m2.partial_block is None
    # diverging inside page 2: page 1 only
    m3 = cache.match(toks[:5] + [99] * 5)
    assert m3.full_blocks == blocks[:1]
    assert cache.hit_rate == 1.0


def test_prefix_cache_eviction_and_live_refs():
    a = BlockedAllocator(16, 4)
    cache = PrefixCache(a)
    toks = list(range(8))
    blocks = a.allocate(2)
    cache.insert(toks, blocks)
    a.free(blocks)                         # original owner finished
    assert a.free_blocks == 14             # cache still holds both

    # a "sequence" shares the leaf page; eviction must not reclaim it
    cache2_owner = [blocks[1]]
    a.incref(cache2_owner)
    assert cache.evict(2) == 2             # trie fully drained (leaf-first)
    assert cache.pages_cached == 0
    assert a.free_blocks == 15             # page 0 back; page 1 still live
    a.free(cache2_owner)
    assert a.free_blocks == 16


def test_prefix_cache_lru_and_exclude():
    a = BlockedAllocator(16, 4)
    cache = PrefixCache(a, max_pages=16)
    b1 = a.allocate(1)
    b2 = a.allocate(1)
    cache.insert([1, 2, 3, 4], b1)
    cache.insert([5, 6, 7, 8], b2)
    cache.match([1, 2, 3, 4])              # freshen b1 → b2 becomes LRU
    assert cache.evict(1) == 1
    assert cache.match([5, 6, 7, 8]).full_blocks == []   # b2 gone
    assert cache.match([1, 2, 3, 4]).full_blocks == b1
    # exclusion protects the named page even when it is the only leaf
    assert cache.evict(1, exclude_blocks=b1) == 0
    assert cache.evict(1) == 1


# ---------------------------------------------------------------------------
# SplitFuse token-budget policy
# ---------------------------------------------------------------------------

def _drain(state, sched, max_rounds=500):
    """Run scheduler rounds until idle; returns per-round picked uids."""
    rounds = []
    for _ in range(max_rounds):
        batch = sched.next_batch()
        if batch is None:
            return rounds
        rounds.append(list(batch.uids))
        sched.mark_scheduled(batch)
    raise AssertionError("scheduler did not drain")


def test_token_budget_policy_mixes_decode_and_prefill():
    st = DSStateManager(max_sequences=8, num_blocks=64, block_size=8)
    pol = TokenBudgetPolicy()
    sched = RaggedScheduler(st, max_batch_tokens=8, prefill_chunk=4,
                            policy=pol)
    st.extend(0, list(range(30)))          # long prefill
    st.extend(1, [1])                      # decode row
    pol.note_arrival(0)
    pol.note_arrival(1)
    picks = pol.select(st, 8, 4)
    assert picks[0] == (1, 1)              # decode rides first
    assert (0, 4) in picks                 # prefill chunk fills the rest


def test_token_budget_policy_starvation_freedom():
    """Late arrivals must not starve the oldest prefill: strict FIFO on
    prefill order + round-robin decodes ⇒ everything drains."""
    st = DSStateManager(max_sequences=16, num_blocks=256, block_size=8)
    pol = TokenBudgetPolicy()
    sched = RaggedScheduler(st, max_batch_tokens=6, prefill_chunk=4,
                            policy=pol)
    for uid in range(10):
        st.extend(uid, list(range(17)))
        pol.note_arrival(uid)
    rounds = _drain(st, sched)
    # uid 0 (oldest) must finish its prefill no later than any newer uid
    last_seen = {u: max(i for i, r in enumerate(rounds) if u in r)
                 for u in range(10)}
    assert last_seen[0] == min(last_seen.values())
    assert all(s.pending == 0 for s in st.seqs.values())


def test_token_budget_policy_decode_round_robin():
    """Budget smaller than the decode population: rotation serves every
    row within a bounded number of steps."""
    st = DSStateManager(max_sequences=8, num_blocks=64, block_size=8)
    pol = TokenBudgetPolicy()
    served = set()
    for uid in range(6):
        st.extend(uid, [uid])
        pol.note_arrival(uid)
    for _ in range(3):                     # 3 rounds x budget 2 = all 6
        for uid, take in pol.select(st, 2, 4):
            served.add(uid)
            st.seqs[uid].seen_tokens += take
        for uid in range(6):               # refill: decode again next round
            if st.seqs[uid].pending == 0:
                st.seqs[uid].seen_tokens -= 1
    assert served == set(range(6))


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------

def test_queue_priority_fifo_and_backpressure():
    q = AdmissionQueue(max_depth=3)
    lo1 = Request(prompt=[1], priority=0)
    lo2 = Request(prompt=[2], priority=0)
    hi = Request(prompt=[3], priority=5)
    q.submit(lo1, now=0.0)
    q.submit(lo2, now=0.0)
    q.submit(hi, now=0.0)
    with pytest.raises(AdmissionError) as exc:
        q.submit(Request(prompt=[4]), now=0.0)
    assert exc.value.reason == "queue_full"
    assert q.pop_next(0.0) is hi           # priority first
    assert q.pop_next(0.0) is lo1          # FIFO within class
    assert q.pop_next(0.0) is lo2


def test_queue_sheds_expired_lowest_priority_when_full():
    q = AdmissionQueue(max_depth=2)
    stale_lo = Request(prompt=[1], priority=0, deadline=1.0)
    stale_hi = Request(prompt=[2], priority=9, deadline=1.0)
    q.submit(stale_lo, now=0.0)
    q.submit(stale_hi, now=0.0)
    fresh = Request(prompt=[3])
    q.submit(fresh, now=5.0)               # both stale: lowest-prio shed
    assert stale_lo.state is RequestState.SHED
    assert stale_lo.finish_reason == "deadline"
    assert stale_hi.state is RequestState.QUEUED
    assert len(q) == 2

    shed = q.shed_expired(now=5.0)
    assert shed == [stale_hi]
    assert q.pop_next(5.0) is fresh


def test_queue_drops_cancelled_on_pop():
    q = AdmissionQueue(max_depth=4)
    r1 = Request(prompt=[1])
    r2 = Request(prompt=[2])
    q.submit(r1, now=0.0)
    q.submit(r2, now=0.0)
    r1.cancel()
    assert q.pop_next(0.0) is r2
    assert r1.state is RequestState.CANCELLED


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_and_metrics_events():
    h = Histogram(lo=0.001, hi=10.0, n_buckets=20)
    for v in (0.01, 0.02, 0.04, 5.0):
        h.record(v)
    assert h.count == 4 and h.vmax == 5.0
    assert h.percentile(50) <= h.percentile(99)
    assert 0.01 <= h.mean <= 5.0

    m = ServingMetrics()
    m.ttft.record(0.5)
    m.bump("admitted", 3)

    class _Mon:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, ev):
            self.events.extend(ev)

    mon = _Mon()
    m.emit(mon, step=7)
    names = {e[0] for e in mon.events}
    assert "serving/ttft_mean" in names and "serving/admitted" in names
    assert all(e[2] == 7 for e in mon.events)


# ---------------------------------------------------------------------------
# engine integration: COW, parity, streaming, SLOs
# ---------------------------------------------------------------------------

def test_cow_block_copies_all_layers(devices):
    eng = _engine(devices)
    alloc = eng.state.allocator
    src = alloc.allocate(1)[0]
    # stamp the source page across every layer's region
    import jax.numpy as jnp
    nl = eng.model_config.num_layers
    stride = eng.arena["k"].shape[0] // nl
    k = np.array(eng.arena["k"])           # writable host copy
    for layer in range(nl):
        k[layer * stride + src] = float(layer + 1)
    eng.arena = {**eng.arena, "k": jnp.asarray(k)}
    dst = eng.cow_block(src)
    assert dst != src and alloc.refcount(dst) == 1
    got = np.asarray(eng.arena["k"])
    for layer in range(nl):
        np.testing.assert_array_equal(got[layer * stride + dst],
                                      got[layer * stride + src])
        assert np.all(got[layer * stride + dst] == float(layer + 1))
    alloc.free([src, dst])


def test_prefix_hit_logits_parity_aligned(devices):
    """A page-aligned prefix hit reruns ONLY the last token and must
    reproduce the cold-prefill logits (same arena values, same program)."""
    eng = _engine(devices)
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, 256, size=17)]  # 2 pages + 1

    cold = eng.put([0], [prompt])[0]
    cache = PrefixCache(eng.state.allocator)
    cache.insert(prompt, eng.state.seqs[0].blocks)

    matched = adopt_cached(eng, cache, 1, prompt)
    assert matched == 16                   # full pages aliased, cap len-1
    assert eng.state.seqs[1].blocks[:2] == eng.state.seqs[0].blocks[:2]
    hit = eng.step()
    assert set(hit) == {1}
    np.testing.assert_allclose(hit[1], cold, rtol=1e-5, atol=1e-6)
    assert int(np.argmax(hit[1])) == int(np.argmax(cold))


def test_prefix_hit_logits_parity_cow_and_generation(devices):
    """A hit through the COW partial page must match cold prefill: same
    last-token logits (tight tolerance — different chunking) and
    token-for-token identical greedy continuation."""
    eng = _engine(devices)
    rng = np.random.default_rng(1)
    base = [int(t) for t in rng.integers(0, 256, size=17)]
    prompt = base + [int(t) for t in rng.integers(0, 256, size=3)]  # len 20

    # warm the cache with the 17-token base (pages 0,1 full; page 2 has 1)
    eng.put([0], [base])
    cache = PrefixCache(eng.state.allocator)
    cache.insert(base, eng.state.seqs[0].blocks)

    matched = adopt_cached(eng, cache, 1, prompt)
    assert matched == 17                   # 2 aliased + COW partial page
    assert eng.state.seqs[1].blocks[2] != eng.state.seqs[0].blocks[2]
    out = {}
    while True:
        r = eng.step()
        if r is None:
            break
        out.update(r)
    hit_logits = out[1]

    cold_eng = _engine(devices, params_key=0)   # same params key ⇒ same model
    cold_logits = cold_eng.put([0], [prompt])[0]
    np.testing.assert_allclose(hit_logits, cold_logits, rtol=1e-4,
                               atol=1e-5)
    assert int(np.argmax(hit_logits)) == int(np.argmax(cold_logits))

    # greedy continuation agrees token-for-token
    def decode(e, uid, first, n):
        toks = [int(first)]
        for _ in range(n - 1):
            nxt = e._put_tokens([uid], [[toks[-1]]])
            toks.append(int(nxt[uid]))
        return toks

    a = decode(eng, 1, np.argmax(hit_logits), 6)
    b = decode(cold_eng, 0, np.argmax(cold_logits), 6)
    assert a == b


def test_frontend_stream_matches_generate(devices):
    """End-to-end: frontend greedy streaming == engine.generate greedy,
    per-token callbacks fire in order, and all pages drain."""
    eng = _engine(devices, params_key=3)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 256, size=n)]
               for n in (5, 12, 19)]

    ref_eng = _engine(devices, params_key=3)
    refs = ref_eng.generate(prompts, max_new_tokens=6)

    fe = ServingFrontend(eng, enable_prefix_cache=True)
    seen = {i: [] for i in range(len(prompts))}
    reqs = [fe.submit(p, max_new_tokens=6,
                      stream_cb=lambda t, i=i: seen[i].append(t))
            for i, p in enumerate(prompts)]
    fe.run_until_idle()

    for i, (req, p, ref) in enumerate(zip(reqs, prompts, refs)):
        assert req.state is RequestState.FINISHED
        expect = [int(t) for t in ref[len(p):]]
        assert req.tokens_out == expect
        assert seen[i] == expect
        assert req.ttft is not None and req.ttft >= 0
    assert not eng.state.seqs              # flushed
    st = fe.stats()
    assert st["completed"] == 3 and st["tokens_out"] == 18
    # prompts were all distinct → pure cold traffic, but pages cached
    assert fe.cache.pages_cached > 0


def test_frontend_stream_matches_solo(devices):
    """A request stream with fewer sequence slots than requests must
    produce token-for-token solo dense-engine outputs, admit queued
    requests as slots free, and release every page at the end."""
    from deepspeed_tpu.inference.engine import init_inference
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    from deepspeed_tpu.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(5))

    rng = np.random.default_rng(9)
    n = 10
    prompts = [rng.integers(0, 256, size=(int(l),), dtype=np.int32)
               for l in rng.integers(4, 24, size=n)]
    budgets = [int(b) for b in rng.integers(2, 40, size=n)]

    v2 = RaggedInferenceEngineTPU(
        cfg, {"dtype": "float32", "num_blocks": 64, "block_size": 16,
              "max_seq_len": 128, "prefill_chunk": 8,
              "max_batch_tokens": 64, "max_sequences": 4},
        params=params)
    fe = ServingFrontend(v2, enable_prefix_cache=False)
    resident = []
    reqs = [fe.submit([int(t) for t in p], max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    while fe.step():
        resident.append(len(v2.state.seqs))
    assert max(resident) == 4              # 4 resident, the rest queued

    v1 = init_inference(cfg, {"dtype": "float32"}, params=params)
    for p, m, req in zip(prompts, budgets, reqs):
        assert req.state is RequestState.FINISHED
        ref = v1.generate(p[None, :], max_new_tokens=m)[0]
        assert req.tokens_out == [int(t) for t in ref[len(p):len(p) + m]]
    assert len(v2.state.seqs) == 0
    assert v2.state.allocator.free_blocks == 64


def test_frontend_prefix_hit_skips_prefill_steps(devices):
    """Second request with a shared prompt adopts cached pages: its
    sequence starts with seen_tokens > 0 and generates the same tokens."""
    eng = _engine(devices, params_key=3)
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(0, 256, size=33)]

    fe = ServingFrontend(eng)
    r1 = fe.submit(prompt, max_new_tokens=4)
    fe.run_until_idle()
    r2 = fe.submit(prompt, max_new_tokens=4)
    fe.run_until_idle()
    assert r2.cached_tokens == 32          # everything but the last token
    assert r2.tokens_out == r1.tokens_out
    assert fe.cache.hit_rate > 0
    assert fe.metrics.counters["prefix_tokens_reused"] == 32


def test_frontend_streaming_iterator_and_cancel(devices):
    eng = _engine(devices, params_key=3)
    fe = ServingFrontend(eng)
    req = fe.submit([1, 2, 3, 4, 5], max_new_tokens=50)
    got = []
    for tok in fe.stream(req):
        got.append(tok)
        if len(got) == 3:
            req.cancel()
    assert req.state is RequestState.CANCELLED
    assert got == req.tokens_out[:len(got)]
    assert len(req.tokens_out) < 50
    assert not eng.state.seqs              # pages released on cancel


def test_frontend_rejects_with_reason(devices):
    eng = _engine(devices, params_key=3, num_blocks=3, max_seq_len=32)
    fe = ServingFrontend(eng, max_queue=1)
    with pytest.raises(AdmissionError) as exc:
        fe.submit(list(range(30)), max_new_tokens=30)   # > max_seq_len
    assert exc.value.reason == "too_long"
    with pytest.raises(AdmissionError) as exc:
        fe.submit([1] * 30, max_new_tokens=2)           # 4 pages > arena
    assert exc.value.reason == "kv_exhausted"
    fe.submit([1, 2, 3], max_new_tokens=1)
    with pytest.raises(AdmissionError) as exc:
        fe.submit([4, 5, 6], max_new_tokens=1)          # bounded queue
    assert exc.value.reason == "queue_full"
    st = fe.stats()
    assert st["rejected_too_long"] == 1
    assert st["rejected_kv_exhausted"] == 1
    assert st["rejected_queue_full"] == 1


def test_frontend_deadline_shed(devices):
    """Past-deadline work is shed — queued and running both — instead of
    stalling the batch (injectable clock keeps this deterministic)."""
    eng = _engine(devices, params_key=3)
    t = [0.0]
    fe = ServingFrontend(eng, clock=lambda: t[0])
    doomed = fe.submit([1, 2, 3], max_new_tokens=4, timeout=5.0)
    ok = fe.submit([4, 5, 6], max_new_tokens=4)
    t[0] = 10.0                            # deadline passes while queued
    fe.run_until_idle()
    assert doomed.state is RequestState.SHED
    assert doomed.finish_reason == "deadline"
    assert ok.state is RequestState.FINISHED
    assert fe.metrics.counters["shed"] == 1

    running = fe.submit([7, 8, 9], max_new_tokens=64, timeout=5.0)
    fe.step()                              # admitted + first token
    assert running.state is RequestState.RUNNING
    t[0] = 20.0                            # expires mid-generation
    fe.run_until_idle()
    assert running.state is RequestState.SHED
    assert not eng.state.seqs


def test_frontend_small_budget_still_drains(devices):
    """Token budget smaller than one prefill chunk: SplitFuse slices the
    work and every request still completes (no starvation, no stall)."""
    eng = _engine(devices, params_key=3)
    fe = ServingFrontend(eng, token_budget=4)
    rng = np.random.default_rng(8)
    reqs = [fe.submit([int(x) for x in rng.integers(0, 256, size=11)],
                      max_new_tokens=3) for _ in range(4)]
    fe.run_until_idle()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert all(len(r.tokens_out) == 3 for r in reqs)
