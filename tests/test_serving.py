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
# PrefixCache makes room with one walk a call: the replaced loop as oracle
# ---------------------------------------------------------------------------

class _ScanAPageCache(PrefixCache):
    """The cache as it made room before it walked the trie once a call
    (ISSUE 53): ``evict`` walks, filters and sorts anew for every page it
    drops and rebuilds the victim's token prefix tier or no tier, and
    ``insert`` calls ``evict(1)`` for every new page of a full cache. Kept
    as the plain reference the one-walk cache must agree with, page for
    page and in order."""

    def _release_page(self, block, tokens):
        if self.tier is not None and tokens:
            if self.tier.capture(tokens, block):
                self.pages_tiered += 1
        self.pages_released += self.allocator.free([block])

    def insert(self, tokens, blocks):
        bs = self.block_size
        self._clock += 1
        node = self._root
        added = 0
        n_full = len(tokens) // bs
        path = set()
        for i in range(n_full):
            key = tuple(tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                if self.pages_cached >= self.max_pages and \
                        self.evict(1, exclude_blocks=path) == 0:
                    return added
                blk = blocks[i]
                self.allocator.incref([blk])
                child = type(node)(key, blk, node)
                node.children[key] = child
                self.pages_cached += 1
                added += 1
            child.last_used = self._clock
            path.add(child.block)
            node = child
        rem = tokens[n_full * bs:]
        if rem and len(blocks) > n_full:
            span = tuple(rem)
            if span not in node.partials:
                if self.pages_cached >= self.max_pages and \
                        self.evict(1, exclude_blocks=path) == 0:
                    return added
                blk = blocks[n_full]
                self.allocator.incref([blk])
                node.partials[span] = [blk, self._clock]
                self.pages_cached += 1
                added += 1
            else:
                node.partials[span][1] = self._clock
        return added

    def evict(self, n_pages, exclude_blocks=()):
        exclude = set(b for b in exclude_blocks if b is not None)
        dropped = 0
        while dropped < n_pages:
            leaves = []
            self._leaves(self._root, leaves)
            leaves = [t for t in leaves
                      if (t[1].partials[t[2]][0] if isinstance(t[2], tuple)
                          else t[2].block) not in exclude]
            if not leaves:
                break
            leaves.sort(key=lambda t: t[0])
            _, parent, what = leaves[0]
            if isinstance(what, tuple):     # partial span key
                self._release_page(parent.partials[what][0],
                                   self._token_path(parent) + list(what))
                del parent.partials[what]
            else:
                self._release_page(what.block, self._token_path(what))
                del parent.children[what.chunk]
            self.pages_cached -= 1
            dropped += 1
        return dropped


class _LoggingAllocator(BlockedAllocator):
    """Every ``free`` in order: the pages a cache let go, as it let go."""

    def __init__(self, *args):
        super().__init__(*args)
        self.freed = []

    def free(self, blocks):
        self.freed.extend(blocks)
        return super().free(blocks)


class _RecordingTier:
    """A page tier that keeps what it was handed and takes two pages in
    three, so ``pages_tiered`` parts from the pages dropped."""

    def __init__(self):
        self.captured, self.invalidated = [], []

    def capture(self, tokens, block):
        self.captured.append((tuple(tokens), block))
        return block % 3 != 0

    def invalidate(self, tokens):
        self.invalidated.append(tuple(tokens))


def _cache_state(cache):
    a = cache.allocator
    return {"pages_cached": cache.pages_cached,
            "owned": cache.owned_blocks(),
            "released": cache.pages_released,
            "tiered": cache.pages_tiered,
            "freed": list(a.freed), "free_blocks": a.free_blocks,
            "refs": [a.refcount(b) for b in range(a.num_blocks)],
            "hits": (cache.lookups, cache.hits, cache.tokens_hit),
            "captured": list(cache.tier.captured) if cache.tier else None,
            "invalidated": list(cache.tier.invalidated)
            if cache.tier else None}


class _CachePair:
    """The one-walk cache and the scan-a-page reference over an allocator
    each, handed the same calls and compared after every one."""

    def __init__(self, num_blocks, block_size, max_pages, tier=False):
        self.caches = [
            cls(_LoggingAllocator(num_blocks, block_size), max_pages,
                tier=_RecordingTier() if tier else None)
            for cls in (PrefixCache, _ScanAPageCache)]
        self.held = []                    # a live sequence's pages, each
        self.ops = 0

    def both(self, call):
        got = [call(c) for c in self.caches]
        self.ops += 1
        assert got[0] == got[1], (self.ops, got)
        new, ref = (_cache_state(c) for c in self.caches)
        assert new == ref, self.ops
        assert len(new["owned"]) == new["pages_cached"]
        return got[0]

    def prompt(self, tokens, keep=False):
        """A request's life: room for its pages (``adopt_cached``'s
        eviction, its matched pages excluded and aliased), its prompt
        cached at the first token, its pages let go — or held, so that a
        later eviction drops a page the allocator does not get back."""
        bs = self.caches[0].block_size

        def run(cache):
            a = cache.allocator
            m = cache.match(tokens)
            need = -(-len(tokens) // bs) - len(m.full_blocks)
            evicted = 0
            if need > a.free_blocks:
                evicted = cache.evict(
                    need - a.free_blocks,
                    exclude_blocks=m.full_blocks + [m.partial_block])
            if need > a.free_blocks:
                return ("no room", evicted)
            a.incref(m.full_blocks)
            blocks = m.full_blocks + a.allocate(need)
            added = cache.insert(tokens, blocks)
            return (evicted, added, blocks)
        out = self.both(run)
        if out[0] == "no room":
            return out
        for cache in self.caches:
            if not keep:
                cache.allocator.free(out[2])
        if keep:
            self.held.append(out[2])
        return out

    def release_held(self):
        if self.held:
            blocks = self.held.pop(0)
            self.both(lambda c: c.allocator.free(blocks))


def _unshared(rng, n_tokens):
    return [int(t) for t in rng.integers(0, 1 << 30, n_tokens)]


def _ties_within_one_chain(rng, pair):
    # every page of a prompt carries one stamp; a match re-stamps a
    # prefix of the chain, so the chain splits into two runs of ties
    prompts = [_unshared(rng, int(n)) for n in rng.integers(9, 40, 6)]
    for p in prompts:
        pair.prompt(p)
    for p in prompts[::2]:
        cut = int(rng.integers(1, len(p)))
        pair.both(lambda c: c.match(p[:cut]).full_blocks)
    while pair.caches[0].pages_cached:
        n = int(rng.integers(1, 4))
        pair.both(lambda c: c.evict(n))


def _stamps_that_tie_across_branches(rng, pair):
    # the cache's own clock stamps one root path a tick, and no two leaves
    # lie on one: leaves that tie need stamps put there by hand. With
    # three values over a branching trie the order among equals is the
    # walk's, and a parent laid bare stands where its last page stood.
    trunks = [_unshared(rng, 12) for _ in range(2)]
    for _ in range(14):
        trunk = trunks[int(rng.integers(2))]
        pair.prompt(trunk[:int(rng.integers(0, 13))] +
                    _unshared(rng, int(rng.integers(1, 14))))
    stamps = [int(t) for t in rng.integers(0, 3, 96)]
    for cache in pair.caches:
        left = iter(stamps)

        def stamp(node):
            for rec in node.partials.values():
                rec[1] = next(left)
            for child in node.children.values():
                child.last_used = next(left)
                stamp(child)
        stamp(cache._root)
    keep = pair.caches[0].owned_blocks()[5:7]
    n = 1
    while pair.both(lambda c: c.evict(n, exclude_blocks=keep)):
        n = int(rng.integers(1, 4))


def _excluded_leaf_shields_ancestors(rng, pair):
    trunk = _unshared(rng, 16)
    prompts = [trunk[:int(rng.integers(4, 17))] +
               _unshared(rng, int(rng.integers(1, 14))) for _ in range(8)]
    for p in prompts:
        pair.prompt(p)
    for _ in range(12):
        p = prompts[int(rng.integers(len(prompts)))]
        n = int(rng.integers(1, 6))

        def run(cache):
            m = cache.match(p)
            return cache.evict(n, exclude_blocks=m.full_blocks +
                               [m.partial_block])
        pair.both(run)
        pair.prompt(prompts[int(rng.integers(len(prompts)))])
    # what the exclusion of one whole path leaves is that path
    p = prompts[0]
    pair.prompt(p)

    def drain(cache):
        m = cache.match(p)
        dropped = cache.evict(10_000, exclude_blocks=m.full_blocks +
                              [m.partial_block])
        path = m.full_blocks + [m.partial_block] * (m.partial_len > 0)
        return dropped, sorted(cache.owned_blocks()) == sorted(path)
    assert pair.both(drain)[1]


def _full_cache_of_unshared_prompts(rng, pair):
    for i in range(40):
        pair.prompt(_unshared(rng, int(rng.integers(5, 60))),
                    keep=rng.random() < 0.3)
        if rng.random() < 0.3:
            pair.release_held()
        if i == 30:
            # a cap lowered under a full cache: a page out for a page in
            assert pair.caches[0].pages_cached == 20
            for cache in pair.caches:
                cache.max_pages = 14
    assert pair.caches[0].pages_cached == 20


def _prompt_that_fits_only_in_part(rng, pair):
    cap, bs = pair.caches[0].max_pages, pair.caches[0].block_size
    for _ in range(6):
        pair.prompt(_unshared(rng, int(rng.integers(5, 30))))
    long = _unshared(rng, (cap + 5) * bs + 3)
    assert pair.prompt(long)[1] == cap     # the pages that fit, no more
    # its cached head matches, and a longer twin adds nothing it can hold
    assert pair.prompt(long + _unshared(rng, 9))[1] == 0
    shared = long[:3 * bs] + _unshared(rng, (cap + 2) * bs)
    assert pair.prompt(shared)[1] == cap - 3
    pair.prompt(_unshared(rng, 11))


def _everything(rng, pair):
    trunks = [_unshared(rng, 24) for _ in range(3)]
    seen = []
    for _ in range(120):
        op = rng.random()
        if op < 0.45 or not seen:
            trunk = trunks[int(rng.integers(3))]
            p = (trunk[:int(rng.integers(0, 25))] if rng.random() < 0.6
                 else []) + _unshared(rng, int(rng.integers(1, 50)))
            seen.append(p)
            pair.prompt(p, keep=rng.random() < 0.25)
        elif op < 0.6:
            p = seen[int(rng.integers(len(seen)))]
            p = p[:int(rng.integers(1, len(p) + 1))]
            pair.both(lambda c: c.match(p).matched(c.block_size))
        elif op < 0.8:
            p = seen[int(rng.integers(len(seen)))]
            n = int(rng.integers(0, 9))

            def run(cache):
                m = cache.match(p)
                return cache.evict(n, exclude_blocks=m.full_blocks +
                                   [m.partial_block])
            pair.both(run)
        elif op < 0.9:
            p = seen[int(rng.integers(len(seen)))]
            pair.both(lambda c: c.invalidate(p))
        else:
            pair.release_held()
    while pair.held:
        pair.release_held()
    pair.both(lambda c: c.clear())
    assert pair.caches[0].allocator.free_blocks == \
        pair.caches[0].allocator.num_blocks


@pytest.mark.parametrize("scenario,kwargs", [
    (_ties_within_one_chain, dict(max_pages=64)),
    (_excluded_leaf_shields_ancestors, dict(max_pages=24)),
    (_full_cache_of_unshared_prompts, dict(max_pages=20)),
    (_prompt_that_fits_only_in_part, dict(max_pages=12)),
    (_everything, dict(max_pages=30, tier=True)),
    (_everything, dict(max_pages=None)),
    (_ties_within_one_chain, dict(max_pages=64, tier=True)),
    (_stamps_that_tie_across_branches, dict(max_pages=80, tier=True)),
], ids=["ties_within_one_chain", "excluded_leaf_shields_ancestors",
        "full_cache_of_unshared_prompts", "prompt_that_fits_only_in_part",
        "tier_random_mix", "random_mix_default_cap", "tier_ties",
        "stamps_that_tie_across_branches"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefix_cache_makes_room_as_the_scan_a_page_loop_did(
        scenario, kwargs, seed):
    """Evicted pages and their order, the allocator's state, the hit
    accounting and a tier's captures, equal to the replaced loop's after
    every call of a seeded sequence."""
    pair = _CachePair(96, 4, **kwargs)
    scenario(np.random.default_rng([seed, 53]), pair)
    assert pair.ops >= 10


def test_prefix_cache_makes_room_with_one_walk_a_call():
    """Counts, not clocks (ROADMAP C13), at cell 11's arena: 2,624 pages
    of 128 tokens, 1,312 of them cached prompts nobody shares."""
    a = BlockedAllocator(2624, 128)
    cache = PrefixCache(a)
    assert cache.max_pages == 1312
    rng = np.random.default_rng(53)
    walks = []
    leaves = cache._leaves
    cache._leaves = lambda node, out: (
        walks.append(node is cache._root), leaves(node, out))[1]
    while cache.pages_cached < cache.max_pages:
        blocks = a.allocate(82)
        cache.insert(_unshared(rng, 82 * 128), blocks)
        a.free(blocks)
    assert (cache.evict_calls, cache.evict_scans, sum(walks)) == (0, 0, 0)

    blocks = a.allocate(160)                # a 20,480-token prompt
    prompt = _unshared(rng, 160 * 128)
    before = cache.pages_evicted
    assert cache.insert(prompt, blocks) == 160
    assert (cache.evict_calls, cache.evict_scans, sum(walks)) == (1, 1, 1)
    assert cache.pages_evicted - before == 160
    assert cache.pages_cached == 1312

    # adopt_cached's room-making at the next admission: evict(k)
    assert cache.evict(100, exclude_blocks=blocks[:3]) == 100
    assert (cache.evict_calls, cache.evict_scans, sum(walks)) == (2, 2, 2)
    assert cache.evict(0) == 0              # nothing to drop: no walk
    assert (cache.evict_calls, cache.evict_scans, sum(walks)) == (2, 2, 2)
    # already cached (the 100 were older prompts'): re-stamped, no room made
    assert cache.insert(prompt, blocks) == 0
    assert (cache.evict_calls, cache.evict_scans, sum(walks)) == (2, 2, 2)


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


def test_frontend_counts_and_spans_the_cache_making_room(devices):
    """An arena of 12 pages, half of it the cache's, under prompts of 7:
    every admission past the first evicts for its pages (`serving/cache_evict`, inside
    `serving/admit`) and every first token publishes a prompt
    (`serving/cache_insert`, inside `serving/fanout`); `stats()` gives the
    cache's three counters, one walk a call."""
    from deepspeed_tpu import telemetry
    eng = _engine(devices, params_key=3, num_blocks=12)
    fe = ServingFrontend(eng)
    rng = np.random.default_rng(53)
    tr = telemetry.tracer
    was = tr.enabled
    tr.configure(enabled=True)
    tr.clear()
    try:
        for _ in range(5):
            fe.submit([int(t) for t in rng.integers(0, 256, size=52)],
                      max_new_tokens=2)
            fe.run_until_idle()
        spans = [e for e in tr.events() if e.get("ph") == "X"]
    finally:
        tr.configure(enabled=was)
        tr.clear()
    stats = fe.stats()
    assert stats["prefix_evict_calls"] == stats["prefix_evict_scans"] >= 3
    assert stats["prefix_pages_evicted"] >= stats["prefix_evict_calls"]

    def inside(name, parent):
        outer = [(e["ts"], e["ts"] + e["dur"]) for e in spans
                 if e["name"] == parent]
        inner = [e for e in spans if e["name"] == name]
        return len(inner), all(
            any(t0 <= e["ts"] and e["ts"] + e["dur"] <= t1
                for t0, t1 in outer) for e in inner)
    assert inside("serving/cache_insert", "serving/fanout") == (5, True)
    n, nested = inside("serving/cache_evict", "serving/admit")
    assert nested and n >= 3


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
