"""Tiered KV cache: HBM → host DRAM → NVMe paging for returning sessions.

Millions of users means millions of *idle* conversations. Their cached
prefixes are pure gold on return (warm resume skips the prefill) but pure
waste while idle — HBM pages pinned by the radix cache are pages decode
batches can't use. This module is the vertical tier underneath
:class:`~deepspeed_tpu.serving.prefix_cache.PrefixCache` that resolves
the tension, the ZeRO-Infinity HBM→DRAM→NVMe offload hierarchy retargeted
from parameters at serving KV:

- **Capture.** When the radix cache evicts a cold leaf (ref count zero in
  the arena, least-recently-used by the cache clock), the page is
  exported host-side FIRST (``engine.export_pages``) and stored in a
  bounded DRAM arena as a checksummed :class:`PageBundle` keyed by the
  exact token prefix it covers — PR 11's export/verify/adopt handoff
  machinery generalized from horizontal (replica→replica) to vertical
  (HBM→host) movement. Optionally EQuARX-style low-precision encoded
  (fp16 / int8 + scale): cold pages tolerate lossy storage because a
  mismatch only costs a slightly different resume, never correctness of
  accounting.
- **Spill.** Past the DRAM high watermark, the least-recently-used
  bundles serialize to an NVMe directory (atomic tmp+rename writes via
  :func:`~deepspeed_tpu.io.async_io.atomic_write`; deliberately not
  fsync'd — see :meth:`KVTier._spill_one`) until usage falls under the
  low watermark. The NVMe level is itself bounded
  (``nvme_max_bytes``); beyond it the coldest entries are dropped — the
  tier degrades to re-prefill, never to an error.
- **Prefetch + adopt.** On the first token of a returning conversation
  (``ServingFrontend.submit``), :meth:`KVTier.issue_prefetch` starts
  async preads of any NVMe-resident chain pages (the PR 6 ``param_stream``
  issue/complete split, retargeted at KV) so the bytes move while the
  request waits in admission; at admission :meth:`KVTier.adopt` drains,
  CRC-verifies, decodes, imports into freshly allocated arena pages and
  re-inserts into the radix cache — the request's normal ``adopt_cached``
  aliasing then skips prefill for everything the tier restored.

Failure domain: a torn spill (CRC mismatch on load — ``kvtier_torn_spill``)
or a stale entry at adoption (``kvtier_stale_adopt``) adopts nothing from
that point in the chain; the request re-prefills the uncovered suffix.
Like handoff, the tier never carries tokens — a lost page costs
recompute, never correctness — and every fault closes the
faults==recoveries ledger with a ``kvtier_reprefill`` recovery.
"""

import json
import os
import struct
import time
import zlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np

from deepspeed_tpu.io.async_io import AsyncIOEngine, atomic_write, \
    pread_retry
from deepspeed_tpu.resilience.faults import fault_injector, record_recovery
from deepspeed_tpu.serving.handoff import PAGE_AXIS, PageBundle, \
    _checksum, verify_bundle

#: spill file header magic — a file that doesn't start with it is torn
_MAGIC = b"DSKV"
_COMPRESS_MODES = ("none", "fp16", "int8")


class TornSpill(RuntimeError):
    """A tier entry failed CRC verification on load (torn spill file or
    corrupted DRAM bundle). The tier drops the entry and the returning
    conversation re-prefills — never adopts garbage KV."""


def _np_dtype(name: str):
    return {"bfloat16": ml_dtypes.bfloat16,
            "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
            "float8_e5m2": ml_dtypes.float8_e5m2}.get(name) or np.dtype(name)


def _encode(pages: Dict[str, np.ndarray], compress: str
            ) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Encode an ``export_pages`` payload for cold storage. ``none`` is
    byte-exact; ``fp16``/``int8`` are the EQuARX-style low-precision
    knobs (per-array symmetric scale for int8) — lossy, which is fine
    for COLD pages whose alternative is not existing at all."""
    if compress not in _COMPRESS_MODES:
        raise ValueError(f"kvtier compress mode {compress!r} "
                         f"(want one of {_COMPRESS_MODES})")
    src_dtype = str(np.asarray(next(iter(pages.values()))).dtype)
    meta: Dict = {"compress": compress, "dtype": src_dtype, "scales": None}
    if compress == "none":
        payload = {k: np.ascontiguousarray(v) for k, v in pages.items()}
    elif compress == "fp16":
        payload = {k: np.asarray(v, np.float32).astype(np.float16)
                   for k, v in pages.items()}
    else:                                   # int8 + per-array scale
        payload, scales = {}, {}
        for k, v in pages.items():
            a = np.asarray(v, np.float32)
            s = float(np.max(np.abs(a)) / 127.0) if a.size else 0.0
            s = s or 1.0
            payload[k] = np.clip(np.round(a / s), -127, 127).astype(np.int8)
            scales[k] = s
        meta["scales"] = scales
    return payload, meta


def _decode(payload: Dict[str, np.ndarray], meta: Dict
            ) -> Dict[str, np.ndarray]:
    dtype = _np_dtype(meta["dtype"])
    compress = meta["compress"]
    if compress == "none":
        return {k: np.asarray(v, dtype) for k, v in payload.items()}
    if compress == "fp16":
        return {k: np.asarray(v, np.float32).astype(dtype)
                for k, v in payload.items()}
    return {k: (np.asarray(v, np.float32) * meta["scales"][k]).astype(dtype)
            for k, v in payload.items()}


@dataclass
class _TierEntry:
    """One page-sized token prefix resident in the tier. ``bundle`` set →
    DRAM-resident; ``path`` set → NVMe-resident (exactly one of the two).
    ``checksum`` is the expected CRC32 of the ENCODED payload bytes, the
    torn detector at every level."""
    key: Tuple[int, ...]
    meta: Dict
    checksum: int
    nbytes: int                      # encoded payload bytes (DRAM cost)
    bundle: Optional[PageBundle] = field(default=None, repr=False)
    path: Optional[str] = None
    file_bytes: int = 0
    arrays: Optional[List[Dict]] = None   # encoded shapes/dtypes for load


def _serialize_entry(entry: _TierEntry) -> bytes:
    """Entry → spill file bytes: magic, u32 header length, JSON header,
    encoded payload arrays in sorted-key order. Self-describing — the
    loader needs nothing but the file (and verifies CRC before trusting
    a byte of payload)."""
    payload = entry.bundle.pages
    arrays = [{"key": k,
               "shape": list(payload[k].shape),
               "dtype": str(payload[k].dtype),
               "nbytes": int(payload[k].nbytes)}
              for k in sorted(payload)]
    header = json.dumps({
        "tokens": list(entry.key), "meta": entry.meta,
        "crc": entry.checksum, "arrays": arrays,
    }).encode()
    parts = [_MAGIC, struct.pack("<I", len(header)), header]
    parts += [np.ascontiguousarray(payload[k]).tobytes()
              for k in sorted(payload)]
    return b"".join(parts)


def _parse_spill(raw: bytes) -> Tuple[Dict, Dict[str, np.ndarray]]:
    """Spill file bytes → (header, payload arrays). Raises
    :class:`TornSpill` on any structural damage or CRC mismatch."""
    if len(raw) < 8 or raw[:4] != _MAGIC:
        raise TornSpill("spill file is not a KV bundle (bad magic)")
    hlen = struct.unpack("<I", raw[4:8])[0]
    if len(raw) < 8 + hlen:
        raise TornSpill("spill file truncated inside header")
    try:
        header = json.loads(raw[8:8 + hlen])
    except ValueError as e:
        raise TornSpill(f"spill header is not valid JSON: {e}") from e
    body = raw[8 + hlen:]
    if zlib.crc32(body) != int(header["crc"]):
        raise TornSpill("spill payload failed CRC32 verification")
    payload: Dict[str, np.ndarray] = {}
    off = 0
    for a in header["arrays"]:
        n = int(a["nbytes"])
        if off + n > len(body):
            raise TornSpill("spill payload truncated")
        payload[a["key"]] = np.frombuffer(
            body[off:off + n], dtype=_np_dtype(a["dtype"])
        ).reshape(a["shape"])
        off += n
    return header, payload


def _count(name: str, by: int = 1, help: str = "") -> None:
    try:
        from deepspeed_tpu import telemetry
        telemetry.registry.counter(name, help=help).inc(by)
    except Exception:                                # noqa: BLE001
        pass


def _event(kind: str, **fields) -> None:
    try:
        from deepspeed_tpu import telemetry
        telemetry.flight_recorder.record_event(kind, **fields)
    except Exception:                                # noqa: BLE001
        pass


class KVTier:
    """The host-side page tier under one frontend's radix cache.

    Entries are keyed by the exact token prefix a page covers (full pages:
    a multiple of ``block_size`` tokens from the root; at most one partial
    extension per chain). LRU order is the :class:`OrderedDict` order —
    every capture/match moves the touched chain to the MRU end, so
    watermark spills and capacity drops always take the coldest
    conversation first, deterministically.
    """

    def __init__(self, engine, dram_bytes: int = 256 << 20,
                 nvme_dir: Optional[str] = None,
                 nvme_max_bytes: Optional[int] = None,
                 high_watermark: float = 0.9, low_watermark: float = 0.7,
                 compress: str = "none",
                 aio: Optional[AsyncIOEngine] = None):
        if not 0.0 < low_watermark <= high_watermark <= 1.0:
            raise ValueError(
                f"kvtier watermarks must satisfy 0 < low <= high <= 1 "
                f"(got low={low_watermark}, high={high_watermark})")
        if compress not in _COMPRESS_MODES:
            raise ValueError(f"kvtier compress mode {compress!r} "
                             f"(want one of {_COMPRESS_MODES})")
        self.engine = engine
        self.block_size = engine.state.allocator.block_size
        self.dram_bytes = int(dram_bytes)
        self.nvme_dir = nvme_dir
        self.nvme_max_bytes = nvme_max_bytes
        self.high_watermark = float(high_watermark)
        self.low_watermark = float(low_watermark)
        self.compress = compress
        self.aio = aio or AsyncIOEngine()
        if nvme_dir:
            os.makedirs(nvme_dir, exist_ok=True)
        #: LRU: oldest first; values are :class:`_TierEntry`
        self._entries: "OrderedDict[Tuple[int, ...], _TierEntry]" = \
            OrderedDict()
        #: full-page prefix → partial keys extending it (chain tails)
        self._partial_index: Dict[Tuple[int, ...], List[Tuple[int, ...]]] \
            = {}
        #: NVMe prefetches in flight: key → destination byte buffer
        self._inflight: Dict[Tuple[int, ...], np.ndarray] = {}
        self._dram_used = 0
        self._nvme_used = 0
        self._spill_seq = 0
        #: adopt-attempt clock — the ``serving_step`` the chaos schedule
        #: triggers ``kvtier_*`` kinds against
        self._ops = 0
        self.counters = {k: 0 for k in (
            "captures", "spills", "adopts", "hits", "misses",
            "torn_spills", "stale_adopts", "fallback_reprefills",
            "dropped", "invalidated", "prefetch_issued",
            "bytes_spilled", "bytes_adopted")}

    # -- capture (PrefixCache eviction sink) --------------------------------

    def capture(self, tokens: List[int], block: int) -> bool:
        """Export one page the radix cache is about to evict into the
        DRAM arena. Called by ``PrefixCache.evict`` BEFORE the allocator
        ref drops — the page's KV is still valid in the arena at export
        time even if another owner keeps the physical page alive after.
        Returns True when the page entered the tier."""
        key = tuple(int(t) for t in tokens)
        if not key:
            return False
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        pages = self.engine.export_pages([block])
        payload, meta = _encode(pages, self.compress)
        crc = _checksum(payload)
        bundle = PageBundle(tokens=list(key), block_size=self.block_size,
                            pages=payload, checksum=crc)
        entry = _TierEntry(key=key, meta=meta, checksum=crc,
                           nbytes=bundle.nbytes, bundle=bundle)
        self._entries[key] = entry
        if len(key) % self.block_size != 0:
            base = key[:len(key) - len(key) % self.block_size]
            self._partial_index.setdefault(base, []).append(key)
        self._dram_used += entry.nbytes
        self.counters["captures"] += 1
        _count("kvtier/evictions",
               help="radix-cache pages captured into the host tier")
        self._maybe_spill()
        self._publish()
        return True

    # -- spill (DRAM watermark → NVMe) --------------------------------------

    def _spill_one(self, entry: _TierEntry) -> bool:
        """DRAM → NVMe for one entry (atomic, deliberately NOT fsync'd:
        tier contents are recomputable cache state — a torn file after a
        crash is caught by the CRC at load and costs one re-prefill, so
        paying a durability barrier per spill in the serving path buys
        nothing). Returns False when there is no NVMe level to spill
        to."""
        if not self.nvme_dir:
            return False
        data = _serialize_entry(entry)
        self._spill_seq += 1
        path = os.path.join(
            self.nvme_dir,
            f"kv-{self._spill_seq:08d}-{entry.checksum & 0xFFFFFFFF:08x}"
            f".bundle")
        atomic_write(path, data, durable=False)
        self._dram_used -= entry.nbytes
        entry.arrays = [{"key": k,
                         "shape": list(entry.bundle.pages[k].shape),
                         "dtype": str(entry.bundle.pages[k].dtype),
                         "nbytes": int(entry.bundle.pages[k].nbytes)}
                        for k in sorted(entry.bundle.pages)]
        entry.bundle = None
        entry.path = path
        entry.file_bytes = len(data)
        self._nvme_used += len(data)
        self.counters["spills"] += 1
        self.counters["bytes_spilled"] += len(data)
        _count("kvtier/spills", help="tier pages spilled DRAM → NVMe")
        _count("kvtier/bytes_spilled", len(data),
               help="bytes written to the NVMe tier level")
        _event("kvtier_spill", pages=1, bytes=len(data))
        return True

    def _maybe_spill(self) -> None:
        """Enforce the DRAM watermark pair: above ``high``, move the
        least-recently-used DRAM-resident entries down (or out) until
        usage is back under ``low`` — hysteresis so a hot eviction burst
        doesn't thrash one page across the boundary."""
        if self._dram_used <= self.high_watermark * self.dram_bytes:
            self._enforce_nvme_bound()
            return
        target = self.low_watermark * self.dram_bytes
        for key in list(self._entries):
            if self._dram_used <= target:
                break
            entry = self._entries[key]
            if entry.bundle is None:
                continue                     # already on NVMe
            if not self._spill_one(entry):
                self._drop(entry, reason="dram_full")
        self._enforce_nvme_bound()

    def _enforce_nvme_bound(self) -> None:
        if self.nvme_max_bytes is None:
            return
        if self._nvme_used <= self.nvme_max_bytes:
            return
        for key in list(self._entries):
            if self._nvme_used <= self.nvme_max_bytes:
                break
            entry = self._entries[key]
            if entry.path is not None:
                self._drop(entry, reason="nvme_full")

    def _drop(self, entry: _TierEntry, reason: str = "") -> None:
        """Remove an entry from every level and index (idempotent)."""
        if self._entries.pop(entry.key, None) is None:
            return
        if entry.bundle is not None:
            self._dram_used -= entry.nbytes
            entry.bundle = None
        if entry.path is not None:
            self._nvme_used -= entry.file_bytes
            try:
                os.unlink(entry.path)
            except OSError:
                pass
            entry.path = None
        if len(entry.key) % self.block_size != 0:
            base = entry.key[:len(entry.key)
                             - len(entry.key) % self.block_size]
            keys = self._partial_index.get(base)
            if keys and entry.key in keys:
                keys.remove(entry.key)
                if not keys:
                    del self._partial_index[base]
        self._inflight.pop(entry.key, None)
        if reason:
            self.counters["dropped"] += 1
            _count("kvtier/dropped",
                   help="tier entries dropped (capacity/stale/torn)")

    def _drop_subtree(self, prefix: Tuple[int, ...]) -> int:
        """Drop every entry whose key extends ``prefix`` (inclusive) —
        a lost or invalidated page orphans every deeper page of its
        chain."""
        doomed = [e for k, e in self._entries.items()
                  if len(k) >= len(prefix) and k[:len(prefix)] == prefix]
        for e in doomed:
            self._drop(e, reason="subtree")
        return len(doomed)

    # -- lookup -------------------------------------------------------------

    def _match_chain(self, prompt: List[int]) -> List[_TierEntry]:
        """Longest contiguous chain of tier entries covering a prefix of
        ``prompt``: full pages from the root, then at most one partial
        extension. Touch refreshes LRU recency."""
        bs = self.block_size
        prompt = [int(t) for t in prompt]
        chain: List[_TierEntry] = []
        i = bs
        while i <= len(prompt):
            entry = self._entries.get(tuple(prompt[:i]))
            if entry is None:
                break
            chain.append(entry)
            i += bs
        covered = i - bs
        best: Optional[Tuple[int, ...]] = None
        for pk in self._partial_index.get(tuple(prompt[:covered]), []):
            if len(pk) <= len(prompt) and tuple(prompt[:len(pk)]) == pk:
                if best is None or len(pk) > len(best):
                    best = pk
        if best is not None:
            chain.append(self._entries[best])
        for entry in chain:
            self._entries.move_to_end(entry.key)
        return chain

    def match_pages(self, prompt: List[int]) -> int:
        """Pages the tier could restore for ``prompt`` (no I/O, no LRU
        touch beyond recency) — the admission planner's tier-pressure
        signal."""
        return len(self._match_chain(prompt))

    # -- prefetch (issue half) ----------------------------------------------

    def issue_prefetch(self, prompt: List[int], ctx=None) -> int:
        """Start async preads for every NVMe-resident page of the
        prompt's chain — fire-and-forget at ``submit`` time so the bytes
        climb to DRAM while the request waits in admission. Returns
        preads issued (0 for an all-DRAM chain: nothing to do). ``ctx``
        (the request's TraceContext) stamps the issue into the request's
        distributed trace."""
        issued = 0
        for entry in self._match_chain(prompt):
            if entry.path is None or entry.key in self._inflight:
                continue
            buf = np.empty(entry.file_bytes, np.uint8)
            self.aio.pread(entry.path, buf, 0)
            self._inflight[entry.key] = buf
            issued += 1
        if issued:
            self.counters["prefetch_issued"] += issued
            _count("kvtier/prefetch_issued", issued,
                   help="NVMe tier preads issued ahead of admission")
            if ctx is not None:
                try:
                    from deepspeed_tpu.telemetry.reqtrace import reqtrace
                    reqtrace.instant("kvtier/prefetch", ctx,
                                     issued=issued)
                except Exception:                    # noqa: BLE001
                    pass
        return issued

    # -- adopt (complete half) ----------------------------------------------

    def _load(self, entry: _TierEntry) -> Dict[str, np.ndarray]:
        """Entry → decoded ``export_pages`` payload, CRC-verified at
        whichever level it lives. Raises :class:`TornSpill`."""
        if entry.bundle is not None:
            if entry.bundle.checksum != entry.checksum or \
                    not verify_bundle(entry.bundle):
                raise TornSpill(f"DRAM bundle for {len(entry.key)}-token "
                                f"prefix failed verification")
            return _decode(entry.bundle.pages, entry.meta)
        buf = self._inflight.pop(entry.key, None)
        if buf is not None:
            raw = buf.tobytes()
        else:
            raw = pread_retry(entry.path, size=entry.file_bytes)
        header, payload = _parse_spill(raw)
        if int(header["crc"]) != entry.checksum:
            raise TornSpill("spill file does not match the tier index "
                            "(stale or swapped file)")
        return _decode(payload, entry.meta)

    def _fallback(self, kind: str, prompt_len: int, ctx=None) -> None:
        """One torn/stale fault handled: the returning conversation will
        re-prefill the uncovered suffix instead. Counts the fallback and
        closes the chaos ledger (one recovery per injected fault). With
        ``ctx``, additionally flags the request's trace interesting —
        kvtier fallbacks are tail-retention causes."""
        self.counters["fallback_reprefills"] += 1
        _count("kvtier/fallback_reprefills",
               help="tier adoptions abandoned for a re-prefill")
        _event("kvtier_fallback", cause=kind, prompt_len=prompt_len)
        record_recovery("kvtier_reprefill", cause=kind,
                        prompt_len=prompt_len)
        if ctx is not None:
            try:
                from deepspeed_tpu.telemetry.reqtrace import reqtrace
                reqtrace.flag(ctx, "kvtier_fallback")
                reqtrace.instant("kvtier/fallback", ctx, cause=kind)
            except Exception:                        # noqa: BLE001
                pass

    def adopt(self, prompt: List[int], cache, ctx=None) -> int:
        """Restore the prompt's tier chain into the arena + radix cache.
        Returns pages the cache now additionally holds (0 → nothing
        restored; the caller's normal prefill covers the rest). Pages
        leave the tier only once the cache owns them — a declined insert
        (page cap) keeps the entry for the next return. ``ctx`` stamps a
        ``kvtier/adopt`` span into the request's distributed trace."""
        t0 = time.monotonic()
        added = self._adopt(prompt, cache, ctx=ctx)
        if ctx is not None and added:
            try:
                from deepspeed_tpu.telemetry.reqtrace import reqtrace
                reqtrace.complete("kvtier/adopt", ctx, t0,
                                  time.monotonic(), pages=added)
            except Exception:                        # noqa: BLE001
                pass
        return added

    def _adopt(self, prompt: List[int], cache, ctx=None) -> int:
        chain = self._match_chain(prompt)
        if not chain:
            if self._entries:
                self.counters["misses"] += 1
                _count("kvtier/misses",
                       help="returning prompts with no tier coverage")
            # advisory=False: a due kvtier fault stays pending for an
            # adopt that actually has a chain to act on
            fault_injector.fire("kvtier", serving_step=self._ops,
                                advisory=False)
            return 0
        self._ops += 1
        advisories = fault_injector.fire("kvtier", serving_step=self._ops,
                                         advisory=True)
        if "kvtier_torn_spill" in advisories:
            # tear the chain root: CRC verification below must catch it
            chain[0].checksum ^= 0x1
            if chain[0].bundle is not None:
                chain[0].bundle.checksum = chain[0].checksum
        if "kvtier_stale_adopt" in advisories:
            # the whole chain is stale by the time we adopt: drop it and
            # force the re-prefill path
            n = len(chain)
            self._drop_subtree(chain[0].key)
            self.counters["stale_adopts"] += n
            _count("kvtier/stale_adopts", n,
                   help="tier entries dropped as stale at adoption")
            self._fallback("kvtier_stale_adopt", len(prompt), ctx=ctx)
            self._publish()
            return 0
        if self._inflight:
            self.aio.drain()
        payloads: List[Dict[str, np.ndarray]] = []
        adopted: List[_TierEntry] = []
        for entry in chain:
            try:
                payloads.append(self._load(entry))
                adopted.append(entry)
            except (TornSpill, OSError) as e:
                # the chain breaks here: deeper pages are orphans
                self.counters["torn_spills"] += 1
                _count("kvtier/torn_spills",
                       help="tier entries lost to torn spills (CRC)")
                self._drop_subtree(entry.key)
                self._fallback("kvtier_torn_spill", len(prompt), ctx=ctx)
                if not isinstance(e, TornSpill):
                    self._drop(entry, reason="io_error")
                break
        if not adopted:
            self.counters["misses"] += 1
            self._publish()
            return 0
        alloc = self.engine.state.allocator
        if len(adopted) > alloc.free_blocks:
            cache.evict(len(adopted) - alloc.free_blocks)
        while adopted and len(adopted) > alloc.free_blocks:
            adopted.pop()                   # trim chain tail under pressure
            payloads.pop()
        if not adopted:
            self.counters["misses"] += 1
            return 0
        pages = {k: np.concatenate([p[k] for p in payloads], axis=PAGE_AXIS)
                 for k in payloads[0]}
        tokens = list(adopted[-1].key)
        blocks = alloc.allocate(len(adopted))
        try:
            self.engine.import_pages(pages, blocks)
            added = cache.insert(tokens, blocks)
        finally:
            alloc.free(blocks)
        nbytes = sum(int(p[k].nbytes) for p in payloads for k in p)
        if added > 0:
            # the cache kept (at least the leading) pages: their tier
            # copies are now redundant — and would go stale the moment
            # the owner decodes into the partial page
            for entry in adopted[:added] if added < len(adopted) \
                    else adopted:
                self._drop(entry)
            self.counters["adopts"] += added
            self.counters["hits"] += 1
            self.counters["bytes_adopted"] += nbytes
            _count("kvtier/adopts", added,
                   help="tier pages restored into the radix cache")
            _count("kvtier/hits", help="returning prompts warm-resumed "
                                       "from the tier")
            _count("kvtier/bytes_adopted", nbytes,
                   help="bytes restored from the host tier")
            _event("kvtier_adopt", pages=added, bytes=nbytes,
                   prompt_len=len(prompt))
        else:
            self.counters["misses"] += 1
            _count("kvtier/misses",
                   help="returning prompts with no tier coverage")
        self._publish()
        return added

    # -- invalidation (stale protection) ------------------------------------

    def invalidate(self, tokens: List[int]) -> int:
        """Drop every tier entry reachable through ``tokens``' first
        chunk — mirrors ``PrefixCache.invalidate``: after an engine
        fault the tier's copies of the suspect prefix are exactly as
        poisonous as the cache's, and a later warm resume from them
        would be the ``kvtier_stale_adopt`` failure for real."""
        tokens = [int(t) for t in tokens]
        bs = self.block_size
        n = 0
        if len(tokens) >= bs:
            n += self._drop_subtree(tuple(tokens[:bs]))
        for key in [k for k in list(self._entries)
                    if len(k) < bs and tuple(tokens[:len(k)]) == k]:
            self._drop(self._entries[key])
            n += 1
        if n:
            self.counters["invalidated"] += n
            _count("kvtier/invalidated", n,
                   help="tier entries dropped by fault invalidation")
            self._publish()
        return n

    # -- accounting ---------------------------------------------------------

    @property
    def dram_pages(self) -> int:
        return sum(1 for e in self._entries.values()
                   if e.bundle is not None)

    @property
    def nvme_pages(self) -> int:
        return sum(1 for e in self._entries.values() if e.path is not None)

    @property
    def total_pages(self) -> int:
        return len(self._entries)

    def _publish(self) -> None:
        try:
            from deepspeed_tpu import telemetry
            g = telemetry.registry.gauge
            g("kvtier/dram_pages",
              help="tier pages resident in host DRAM").set(self.dram_pages)
            g("kvtier/dram_bytes",
              help="host-DRAM arena bytes in use").set(self._dram_used)
            g("kvtier/nvme_pages",
              help="tier pages resident on NVMe").set(self.nvme_pages)
            g("kvtier/nvme_bytes",
              help="NVMe tier bytes in use").set(self._nvme_used)
        except Exception:                            # noqa: BLE001
            pass

    def stats(self) -> Dict[str, int]:
        out = dict(self.counters)
        out.update(dram_pages=self.dram_pages, nvme_pages=self.nvme_pages,
                   dram_bytes=self._dram_used, nvme_bytes=self._nvme_used,
                   total_pages=self.total_pages)
        return out

    def close(self) -> None:
        """Drain in-flight preads and release buffers. Spill files stay
        on disk only while indexed; a closed tier clears its index (a
        fresh process can't trust another's arena geometry anyway)."""
        if self._inflight:
            self.aio.drain()
            self._inflight.clear()
        for entry in list(self._entries.values()):
            self._drop(entry)
        self._publish()
