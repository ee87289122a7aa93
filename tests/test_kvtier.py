"""Tiered KV cache (serving/kvtier.py): HBM → host DRAM → NVMe paging.

Unit tests pin the storage mechanics down with a stub engine — encode/
decode modes, the DSKV spill-file format's torn detection, deterministic
LRU watermark spills and capacity drops, and the split eviction
accounting of a shared CoW prefix (tiered vs released must never
double-count the pool). Engine-backed tests prove the acceptance
properties: an evict→DRAM→NVMe→prefetch→adopt round trip restores the
arena pages BYTE-EXACT; a returning conversation warm-resumes through
the frontend with exact argmax parity and fewer engine steps than a
re-prefill; and the two chaos kinds (`kvtier_torn_spill` /
`kvtier_stale_adopt`) fall back to re-prefill with zero token loss and
a balanced faults==recoveries ledger.
"""

import os
import types

import numpy as np
import pytest
import jax

from deepspeed_tpu.inference.ragged import BlockedAllocator
from deepspeed_tpu.io.async_io import atomic_write, pread_retry
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience.faults import fault_injector
from deepspeed_tpu.serving import KVTier, TornSpill
from deepspeed_tpu.serving.kvtier import (_decode, _encode, _parse_spill,
                                          _serialize_entry)
from deepspeed_tpu.serving.prefix_cache import PrefixCache


@pytest.fixture(autouse=True)
def _disarm():
    fault_injector.disarm()
    fault_injector.last_step = None
    yield
    fault_injector.disarm()
    fault_injector.last_step = None


def _counter(name: str) -> float:
    from deepspeed_tpu import telemetry
    return telemetry.registry.counter(name).value


# ---------------------------------------------------------------------------
# stub engine: export traceable by block id, import recorded
# ---------------------------------------------------------------------------

BS = 4                                   # stub block size (tokens/page)


class _StubEngine:
    """export_pages fills every element with the block id, so adopted
    bytes are traceable back to the exact page that was captured."""

    def __init__(self, num_blocks=16):
        self.state = types.SimpleNamespace(
            allocator=BlockedAllocator(num_blocks, BS))
        self.imported = []

    def export_pages(self, blocks):
        m = len(blocks)
        out = {}
        for key, bias in (("k", 0.0), ("v", 0.5)):
            a = np.empty((2, m, BS, 2), np.float32)
            for j, b in enumerate(blocks):
                a[:, j] = float(b) + bias
            out[key] = a
        return out

    def import_pages(self, pages, blocks):
        self.imported.append(({k: np.asarray(v) for k, v in pages.items()},
                              list(blocks)))


def _tier(eng, tmp_path=None, **kw):
    kw.setdefault("dram_bytes", 1 << 20)
    if tmp_path is not None:
        kw.setdefault("nvme_dir", str(tmp_path / "nvme"))
    return KVTier(eng, **kw)


def _keyed(prompt):
    return [int(t) for t in prompt]


# ---------------------------------------------------------------------------
# encode / decode + spill-file format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["none", "fp16", "int8"])
def test_encode_decode_roundtrip(mode):
    rng = np.random.default_rng(0)
    pages = {"k": rng.standard_normal((2, 3, BS, 2)).astype(np.float32),
             "v": rng.standard_normal((2, 3, BS, 2)).astype(np.float32)}
    payload, meta = _encode(pages, mode)
    back = _decode(payload, meta)
    assert set(back) == {"k", "v"}
    for k in pages:
        assert back[k].dtype == pages[k].dtype
        assert back[k].shape == pages[k].shape
        if mode == "none":
            assert back[k].tobytes() == pages[k].tobytes()
        else:
            tol = 2e-3 if mode == "fp16" else 5e-2
            assert np.max(np.abs(back[k] - pages[k])) < tol
    with pytest.raises(ValueError):
        _encode(pages, "gzip")


def test_spill_file_roundtrip_and_torn_detection():
    eng = _StubEngine()
    tier = _tier(eng)
    key = tuple(range(BS))
    assert tier.capture(list(key), 5)
    entry = tier._entries[key]
    raw = _serialize_entry(entry)
    header, payload = _parse_spill(raw)
    assert header["tokens"] == list(key)
    assert payload["k"].tobytes() == entry.bundle.pages["k"].tobytes()
    # one flipped payload byte → CRC catches it
    torn = bytearray(raw)
    torn[-1] ^= 0xFF
    with pytest.raises(TornSpill):
        _parse_spill(bytes(torn))
    with pytest.raises(TornSpill):
        _parse_spill(raw[: len(raw) // 2])          # truncated payload
    with pytest.raises(TornSpill):
        _parse_spill(b"NOPE" + raw[4:])             # bad magic
    with pytest.raises(TornSpill):
        _parse_spill(raw[:6])                       # truncated header


# ---------------------------------------------------------------------------
# io/async_io helpers (shared with the checkpoint store)
# ---------------------------------------------------------------------------

def test_atomic_write_no_tmp_leftovers(tmp_path):
    path = tmp_path / "latest"
    atomic_write(str(path), b"tag-a")
    atomic_write(str(path), b"tag-b", durable=False)
    assert path.read_bytes() == b"tag-b"
    assert os.listdir(tmp_path) == ["latest"]       # tmp files cleaned up


def test_pread_retry_transient_and_missing(tmp_path):
    path = tmp_path / "frag"
    path.write_bytes(b"payload-bytes")
    calls = {"n": 0}

    def flaky(p, mode="rb"):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient")
        return open(p, mode)

    out = pread_retry(str(path), backoff_s=0.0, _open=flaky)
    assert out == b"payload-bytes" and calls["n"] == 2
    assert pread_retry(str(path), size=7, offset=3,
                       backoff_s=0.0) == b"load-by"

    # a missing file is NOT transient: no retry, immediate raise
    misses = {"n": 0}

    def gone(p, mode="rb"):
        misses["n"] += 1
        raise FileNotFoundError(p)

    with pytest.raises(FileNotFoundError):
        pread_retry(str(path), retries=5, backoff_s=0.0, _open=gone)
    assert misses["n"] == 1

    def always(p, mode="rb"):
        raise OSError("disk on fire")

    with pytest.raises(OSError, match="disk on fire"):
        pread_retry(str(path), retries=2, backoff_s=0.0, _open=always)


# ---------------------------------------------------------------------------
# tier mechanics (stub engine)
# ---------------------------------------------------------------------------

def test_capture_spill_prefetch_adopt_roundtrip(tmp_path):
    """The full vertical trip: capture → forced NVMe spill → async
    prefetch at submit → adopt restores the exact bytes and hands page
    ownership to the radix cache."""
    eng = _StubEngine()
    alloc = eng.state.allocator
    cache = PrefixCache(alloc)
    # one page fits under high*dram_bytes, so every capture spills the
    # PREVIOUS page — both chain pages end on NVMe after a third capture
    page_bytes = 2 * (2 * 1 * BS * 2) * 4
    tier = _tier(eng, tmp_path, dram_bytes=2 * page_bytes,
                 high_watermark=0.5, low_watermark=0.25)
    cache.tier = tier

    k1 = list(range(BS))
    k2 = k1 + list(range(10, 10 + BS))
    assert tier.capture(k1, 5)
    assert tier.capture(k2, 6)
    assert tier.capture(k2, 6) is False             # duplicate key
    tier.capture(list(range(20, 20 + BS)), 7)       # pushes k1+k2 to NVMe
    assert tier.nvme_pages == 2 and tier.dram_pages == 1
    spill_files = os.listdir(tmp_path / "nvme")
    assert len(spill_files) == 2

    prompt = k2 + [99]
    assert tier.match_pages(prompt) == 2
    assert tier.issue_prefetch(prompt) == 2
    assert tier.issue_prefetch(prompt) == 0         # already in flight

    added = tier.adopt(prompt, cache)
    assert added == 2
    pages, blocks = eng.imported[-1]
    assert pages["k"].shape == (2, 2, BS, 2)
    assert np.all(pages["k"][:, 0] == 5.0)          # byte-exact, in order
    assert np.all(pages["k"][:, 1] == 6.0)
    assert np.all(pages["v"][:, 1] == 6.5)
    # the cache is now the pages' only owner
    assert cache.pages_cached == 2
    assert alloc.live_blocks == 2 and alloc.total_refs() == 2
    # adopted entries left the tier; a re-adopt is a no-op (idempotent)
    assert tier.adopt(prompt, cache) == 0
    assert cache.pages_cached == 2 and alloc.total_refs() == 2
    assert cache.match(k2).full_blocks == blocks
    st = tier.stats()
    assert st["spills"] == 2 and st["adopts"] == 2 and st["hits"] == 1
    assert st["prefetch_issued"] == 2
    tier.close()
    assert os.listdir(tmp_path / "nvme") == []      # index gone → files gone


def test_lru_watermark_order_deterministic(tmp_path):
    """Watermark enforcement always takes the least-recently-used entry
    first, and a match refreshes recency — deterministically."""
    eng = _StubEngine()
    page_bytes = 2 * (2 * 1 * BS * 2) * 4
    tier = _tier(eng, tmp_path, dram_bytes=3 * page_bytes,
                 high_watermark=0.67, low_watermark=0.34)
    ka = list(range(BS))
    kb = list(range(100, 100 + BS))
    kc = list(range(200, 200 + BS))
    tier.capture(ka, 1)
    tier.capture(kb, 2)
    tier.match_pages(ka + [7])                     # refresh A: B is now LRU
    tier.capture(kc, 3)                            # breach → spill to low
    assert tier._entries[tuple(kb)].path is not None     # B spilled first
    assert tier._entries[tuple(ka)].path is not None     # then A
    assert tier._entries[tuple(kc)].bundle is not None   # newest stays hot

    # with no NVMe level, the same pressure DROPS oldest-first instead
    # (low == high: drain exactly back under the threshold)
    tier2 = KVTier(_StubEngine(), dram_bytes=3 * page_bytes,
                   high_watermark=0.67, low_watermark=0.67)
    tier2.capture(ka, 1)
    tier2.capture(kb, 2)
    tier2.capture(kc, 3)
    assert list(tier2._entries) == [tuple(kb), tuple(kc)]
    assert tier2.counters["dropped"] == 1

    # bounded NVMe level: over budget drops the coldest spilled entry
    tier3 = _tier(_StubEngine(), tmp_path / "b", dram_bytes=page_bytes,
                  high_watermark=0.5, low_watermark=0.25,
                  nvme_max_bytes=1)
    tier3.capture(ka, 1)
    tier3.capture(kb, 2)                           # ka spills, then drops
    assert tuple(ka) not in tier3._entries
    assert tier3.counters["spills"] >= 1 and tier3.counters["dropped"] >= 1


def test_cow_shared_prefix_split_accounting():
    """Satellite regression: evicting a page a live sequence still
    shares reports tiered +1 / released +0 (free pool unchanged), and
    the evict→re-adopt round trip restores exact refcount/free-block
    totals — nothing double-counted."""
    eng = _StubEngine(num_blocks=8)
    alloc = eng.state.allocator
    cache = PrefixCache(alloc)
    cache.tier = _tier(eng)

    blocks = alloc.allocate(1)              # ref 1: the live sequence
    tokens = list(range(BS))
    assert cache.insert(tokens, blocks) == 1        # ref 2: the cache
    assert alloc.total_refs() == 2 and alloc.free_blocks == 7

    assert cache.evict(1) == 1
    # page captured to the tier but NOT reclaimed — the sequence lives
    assert cache.pages_tiered == 1 and cache.pages_released == 0
    assert alloc.free_blocks == 7 and alloc.live_blocks == 1
    alloc.free(blocks)                      # the sequence finishes
    assert alloc.free_blocks == 8

    added = cache.tier.adopt(tokens + [99], cache)
    assert added == 1
    assert cache.pages_cached == 1
    assert alloc.live_blocks == 1 and alloc.total_refs() == 1
    assert alloc.free_blocks == 7
    # and evicting the sole-owner copy DOES release it, once — and
    # re-captures it (adoption dropped the tier's now-redundant copy)
    assert cache.evict(1) == 1
    assert cache.pages_released == 1 and alloc.free_blocks == 8
    assert cache.pages_tiered == 2 and cache.tier.total_pages == 1


def test_invalidate_drops_tier_copies():
    """Fault invalidation reaches the tier: the suspect prefix's cached
    AND tiered copies go, and the fault path never captures."""
    eng = _StubEngine(num_blocks=8)
    alloc = eng.state.allocator
    cache = PrefixCache(alloc)
    tier = _tier(eng)
    cache.tier = tier

    tokens = list(range(2 * BS))
    tier.capture(tokens[:BS], 3)
    tier.capture(tokens, 4)
    blocks = alloc.allocate(2)
    cache.insert(tokens, blocks)
    alloc.free(blocks)
    caps0 = tier.counters["captures"]

    dropped = cache.invalidate(tokens)
    assert dropped == 2
    assert tier.total_pages == 0
    assert tier.counters["invalidated"] == 2
    assert tier.counters["captures"] == caps0       # suspect KV: no capture
    assert alloc.free_blocks == 8
    # cache-side split accounting survived the subtree free
    assert cache.pages_released == 2


def test_torn_dram_bundle_falls_back():
    """A corrupted DRAM-resident bundle is caught at adopt (verify) and
    the chain is dropped — adopt returns 0, one fallback is counted."""
    eng = _StubEngine()
    cache = PrefixCache(eng.state.allocator)
    tier = _tier(eng)
    tokens = list(range(BS))
    tier.capture(tokens, 5)
    tier._entries[tuple(tokens)].bundle.pages["k"][0, 0, 0, 0] += 1.0
    assert tier.adopt(tokens + [1], cache) == 0
    assert tier.total_pages == 0
    assert tier.counters["torn_spills"] == 1
    assert tier.counters["fallback_reprefills"] == 1


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------

def test_kvtier_config_validation():
    from deepspeed_tpu.config import DeepSpeedTPUConfig, KVTierConfig
    cfg = KVTierConfig()
    assert cfg.enabled is False and cfg.compress == "none"
    assert cfg.high_watermark == 0.9 and cfg.low_watermark == 0.7
    with pytest.raises(Exception):
        KVTierConfig(low_watermark=0.95, high_watermark=0.9)
    with pytest.raises(Exception):
        KVTierConfig(compress="gzip")
    full = DeepSpeedTPUConfig(train_batch_size=1,
                              kvtier={"enabled": True, "nvme_dir": "/x"})
    assert full.kvtier.enabled and full.kvtier.nvme_dir == "/x"
    with pytest.raises(ValueError):
        KVTier(_StubEngine(), high_watermark=0.2, low_watermark=0.5)


# ---------------------------------------------------------------------------
# fleet / dstpu-top surface
# ---------------------------------------------------------------------------

def test_fleet_kvtier_row_and_render():
    from deepspeed_tpu.telemetry.fleet import kvtier_state, render_table
    st = kvtier_state({"kvtier_dram_pages": 3.0, "kvtier_nvme_pages": 40.0,
                       "kvtier_hits": 7, "kvtier_spills": 41.0,
                       "kvtier_adopts": 12.0})
    assert st == {"dram": 3.0, "nvme": 40.0, "hits": 7.0,
                  "spills": 41.0, "adopts": 12.0}
    assert kvtier_state({"serving_admitted": 5}) is None
    text = render_table([{"host": "h0", "status": "ok", "kvtier": st}])
    assert "└─ kvtier:" in text and "nvme=40" in text


# ---------------------------------------------------------------------------
# engine-backed: byte-exact round trip, warm resume, chaos drills
# ---------------------------------------------------------------------------

SRV_CFG = {"dtype": "float32", "num_blocks": 32, "block_size": 8,
           "max_seq_len": 128, "prefill_chunk": 8, "max_batch_tokens": 64,
           "max_sequences": 16}


def _engine(devices, params=None):
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceEngineTPU
    from deepspeed_tpu.models.llama import llama3_config
    from deepspeed_tpu.models.transformer import init_params
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = llama3_config("tiny", max_seq_len=256, vocab_size=256)
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(0))
    return RaggedInferenceEngineTPU(cfg, dict(SRV_CFG), params=params)


def test_engine_evict_adopt_byte_exact(devices, tmp_path):
    """Acceptance: evict → DRAM → NVMe → prefetch → adopt restores the
    arena pages byte-for-byte through the real export/import path."""
    eng = _engine(devices)
    alloc = eng.state.allocator
    bs = alloc.block_size
    cache = PrefixCache(alloc)
    tier = KVTier(eng, dram_bytes=eng.kv_page_nbytes(),  # force spills
                  nvme_dir=str(tmp_path / "nvme"),
                  high_watermark=0.5, low_watermark=0.25)
    cache.tier = tier

    rng = np.random.default_rng(1)
    blocks = alloc.allocate(2)
    L = eng.model_config.num_layers
    pages = {k: rng.standard_normal(
        (L, 2) + eng.arena[k].shape[1:]).astype(np.float32)
        for k in ("k", "v")}
    eng.import_pages(pages, blocks)
    tokens = list(range(2 * bs))
    assert cache.insert(tokens, blocks) == 2
    alloc.free(blocks)

    free0 = alloc.free_blocks
    assert cache.evict(2) == 2
    assert alloc.free_blocks == free0 + 2           # arena fully reclaimed
    assert tier.nvme_pages >= 1                     # spill really happened

    prompt = tokens + [5]
    tier.issue_prefetch(prompt)
    assert tier.adopt(prompt, cache) == 2
    match = cache.match(tokens)
    assert len(match.full_blocks) == 2
    restored = eng.export_pages(match.full_blocks)
    for k in pages:
        assert restored[k].tobytes() == pages[k].tobytes()
    assert alloc.total_refs() == 2                  # cache is sole owner


def test_frontend_warm_resume_parity_and_fewer_steps(devices):
    """A returning conversation served through the frontend: the tier
    restores its pages (hits>=1), the tokens match a tierless re-prefill
    run exactly, and the warm return takes fewer engine steps."""
    from deepspeed_tpu.serving import ServingFrontend
    prompt = [3 + i for i in range(16)]
    new, follow = 4, 6

    def run(cfg):
        fe = ServingFrontend(_engine(devices), config=cfg)
        r1 = fe.submit(prompt, max_new_tokens=new)
        fe.run_until_idle()
        fe.cache.evict(1 << 30)                     # the session idles
        steps0 = fe.metrics.counters["engine_steps"]
        folded = prompt + list(r1.tokens_out) + [9] * follow
        r2 = fe.submit(folded, max_new_tokens=new)
        fe.run_until_idle()
        steps = fe.metrics.counters["engine_steps"] - steps0
        stats = fe.stats()
        fe.close()
        return list(r1.tokens_out), list(r2.tokens_out), steps, stats

    cold = run(None)
    warm = run({"kvtier": {"enabled": True, "dram_bytes": 1 << 22}})
    assert warm[0] == cold[0] and warm[1] == cold[1]      # exact parity
    assert warm[2] < cold[2]                              # fewer steps
    kv = warm[3]["kvtier"]
    assert kv["hits"] >= 1 and kv["adopts"] >= 1
    assert "kvtier" not in cold[3]


@pytest.mark.parametrize("kind", ["kvtier_torn_spill", "kvtier_stale_adopt"])
def test_kvtier_chaos_fallback_parity_and_ledger(devices, kind):
    """Acceptance for the tier failure domain: with a torn spill or a
    stale adoption injected, the returning conversation still produces
    the exact tierless tokens (re-prefill, zero token loss), the
    faults==recoveries ledger closes, and the doctor renders the
    fallback + recovery."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.serving import ServingFrontend
    from deepspeed_tpu.telemetry.doctor import analyze, render
    prompt = [40 + i for i in range(16)]
    new, follow = 4, 6

    fe0 = ServingFrontend(_engine(devices))
    r1 = fe0.submit(prompt, max_new_tokens=new)
    fe0.run_until_idle()
    folded = prompt + list(r1.tokens_out) + [9] * follow
    fe0.cache.evict(1 << 30)
    r2 = fe0.submit(folded, max_new_tokens=new)
    fe0.run_until_idle()
    expected = (list(r1.tokens_out), list(r2.tokens_out))
    fe0.close()

    f0 = _counter("resilience/faults_injected")
    c0 = _counter("resilience/recoveries")
    n0 = len(telemetry.flight_recorder.snapshot().get("events", []))
    fe = ServingFrontend(_engine(devices),
                         config={"kvtier": {"enabled": True,
                                            "dram_bytes": 1 << 22}})
    try:
        w1 = fe.submit(prompt, max_new_tokens=new)
        fe.run_until_idle()
        fe.cache.evict(1 << 30)
        assert fe.kvtier.total_pages >= 1
        fault_injector.arm(f"serving_step:1:{kind}:kvtier", _env=False)
        w2 = fe.submit(folded, max_new_tokens=new)
        fe.run_until_idle()
        assert (list(w1.tokens_out), list(w2.tokens_out)) == expected
        assert w2.finish_reason == "length"
        assert _counter("resilience/faults_injected") - f0 == 1
        assert _counter("resilience/recoveries") - c0 == 1
        st = fe.kvtier.stats()
        assert st["fallback_reprefills"] == 1 and st["hits"] == 0
        if kind == "kvtier_torn_spill":
            assert st["torn_spills"] == 1
        else:
            assert st["stale_adopts"] >= 1
        events = telemetry.flight_recorder.snapshot().get(
            "events", [])[n0:]
        assert any(e["kind"] == "kvtier_fallback" and e["cause"] == kind
                   for e in events)
        report = analyze([{"meta": {"hostname": "h0"}, "steps": [],
                           "events": events}], [])
        assert report["resilience"]["unrecovered"] == 0
        text = render(report)
        assert "kvtier_fallback" in text
        assert "kvtier_reprefill" in text
    finally:
        fault_injector.disarm()
        fe.close()
