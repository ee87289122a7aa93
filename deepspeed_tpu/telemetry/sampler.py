"""Resource samplers: device-memory watermarks and MFU accounting.

Memory: jax device ``memory_stats()`` where the backend reports it (TPU,
GPU), falling back to summing live device buffers, falling back to
nothing — plus host RSS from /proc (psutil when available). Every path
degrades to a clean no-op; sampling must never take a training loop down.

MFU: achieved FLOPs/s/chip over peak, with the bf16 peak-FLOPs table
keyed by TPU platform generation (public chip specs — the same numbers
``bench.py`` has always used; this module is now their home).
"""

import os
from typing import Any, Dict, Optional

from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.registry import registry as _global_registry

#: bf16 peak FLOPs/s per chip by device kind substring (public TPU specs)
PEAK_FLOPS_BF16: Dict[str, float] = {
    "v7": 2307e12, "ironwood": 2307e12,
    "v6e": 918e12, "trillium": 918e12,
    "v5p": 459e12,
    "v5e": 197e12, "v5 lite": 197e12, "v5litepod": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

#: peak HBM bandwidth, bytes/s per chip (public TPU specs; the memory
#: side of the roofline — see telemetry/explain.py)
PEAK_HBM_BW: Dict[str, float] = {
    "v7": 7370e9, "ironwood": 7370e9,
    "v6e": 1640e9, "trillium": 1640e9,
    "v5p": 2765e9,
    "v5e": 819e9, "v5 lite": 819e9, "v5litepod": 819e9,
    "v4": 1228e9,
    "v3": 900e9,
    "v2": 700e9,
}

#: HBM capacity, bytes per chip (public TPU specs; v2/v3 listed per core
#: — jax exposes cores as devices there). Used as the budget ceiling when
#: the backend doesn't report ``memory_stats()['bytes_limit']``.
HBM_CAPACITY: Dict[str, float] = {
    "v7": 192 * 2**30, "ironwood": 192 * 2**30,
    "v6e": 32 * 2**30, "trillium": 32 * 2**30,
    "v5p": 95 * 2**30,
    "v5e": 16 * 2**30, "v5 lite": 16 * 2**30, "v5litepod": 16 * 2**30,
    "v4": 32 * 2**30,
    "v3": 16 * 2**30,
    "v2": 8 * 2**30,
}

#: platforms the user has already been warned about (once per process);
#: see :func:`warn_unknown_platform`
_warned_platforms: set = set()


def known_platforms() -> list:
    """Sorted spec-table keys — the ``--platform`` values that resolve to
    non-zero peaks (every table is keyed identically)."""
    return sorted(PEAK_FLOPS_BF16)


def warn_unknown_platform(name: str, context: str = "roofline") -> bool:
    """One-time (per process, per name) warning for a ``--platform``
    string that matches no spec-table entry. Returns True when the
    platform IS unknown — callers degrade to zero peaks / unknown-bound
    scoring instead of raising (an autotune sweep must not abort on a
    typo'd or future chip name). 'cpu' is silently unknown by design."""
    key = str(name).lower()
    if key in ("", "cpu", "none"):
        return key != ""
    if any(k in key for k in PEAK_FLOPS_BF16):
        return False
    if key not in _warned_platforms:
        _warned_platforms.add(key)
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "unknown platform %r for %s — no peak numbers in the spec "
            "tables (known: %s); peaks read 0 and predictions degrade "
            "to unknown-bound", name, context,
            ", ".join(known_platforms()))
    return True


def _lookup(table: Dict[str, float], device: Any) -> float:
    """Spec-table entry for ``device``'s kind. A TPU whose ``device_kind``
    matches no key is an error — a peak of 0 would make MFU and every
    roofline share read 0 instead of failing. Non-TPU devices (CPU, the
    tests' stubs) read 0.0: those numbers are not meaningful there."""
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            return 0.0
    kind = str(getattr(device, "device_kind", "cpu")).lower()
    for key, val in table.items():
        if key in kind:
            return val
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"TPU device_kind {getattr(device, 'device_kind', None)!r} "
            f"matches no entry of the peak tables (known: "
            f"{', '.join(known_platforms())}); add its published peaks to "
            f"telemetry/sampler.py")
    return 0.0


def peak_flops(device: Any = None) -> float:
    """Peak bf16 FLOPs/s for ``device`` (default: first jax device).
    0.0 off TPU — MFU is not meaningful there; raises for a TPU that is
    not in the table."""
    return _lookup(PEAK_FLOPS_BF16, device)


def peak_hbm_bw(device: Any = None) -> float:
    """Peak HBM bytes/s for ``device`` (default: first jax device).
    0.0 off TPU; raises for a TPU that is not in the table."""
    return _lookup(PEAK_HBM_BW, device)


def hbm_capacity(device: Any = None) -> float:
    """Per-device HBM bytes: the backend's ``bytes_limit`` when reported
    (the allocator's real ceiling), else the spec-sheet table, else 0.0
    (CPU/unknown — no budget ceiling to check against)."""
    stats = device_memory_stats(device)
    if stats and stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    return _lookup(HBM_CAPACITY, device)


def mfu(flops: float, seconds: float, n_devices: int = 1,
        peak: Optional[float] = None) -> float:
    """Model FLOPs utilization: ``flops`` (total model FLOPs for the
    measured interval, all chips) executed in ``seconds`` over
    ``n_devices`` chips of ``peak`` FLOPs/s each. Returns 0.0 whenever
    the ratio is undefined (no peak known, zero interval)."""
    if seconds <= 0.0 or flops <= 0.0:
        return 0.0
    peak = peak_flops() if peak is None else peak
    if not peak:
        return 0.0
    return flops / seconds / (max(1, n_devices) * peak)


def device_memory_stats(device: Any = None) -> Optional[Dict[str, float]]:
    """``device.memory_stats()`` as floats, or None when the backend does
    not implement it (CPU) or jax is unavailable."""
    try:
        import jax
        device = device if device is not None else jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: float(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


def live_buffer_bytes() -> Optional[float]:
    """Total bytes of live jax arrays (the ``live_buffers`` fallback when
    ``memory_stats`` is unavailable). Counts global logical bytes."""
    try:
        import jax
        return float(sum(getattr(x, "nbytes", 0)
                         for x in jax.live_arrays()))
    except Exception:
        return None


def host_rss_bytes() -> Optional[float]:
    """Host resident-set size in bytes (psutil, else /proc/self/statm)."""
    try:
        import psutil
        return float(psutil.Process().memory_info().rss)
    except Exception:
        pass
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return float(pages * os.sysconf("SC_PAGE_SIZE"))
    except Exception:
        return None


class MemorySampler:
    """Samples device + host memory into ``mem/*`` gauges.

    ``mem/device_bytes_in_use`` — current device allocation (from
    ``memory_stats`` or the live-buffer sum); ``mem/device_peak_bytes`` —
    high-watermark (backend-reported peak when available, else the max
    sample seen); ``mem/host_rss_bytes`` — process RSS. Missing sources
    are skipped, never raised.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._reg = registry if registry is not None else _global_registry
        self._peak = 0.0

    def sample(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        stats = device_memory_stats()
        in_use = stats.get("bytes_in_use") if stats else None
        if in_use is None:
            in_use = live_buffer_bytes()
        if in_use is not None:
            backend_peak = (stats or {}).get("peak_bytes_in_use", 0.0)
            self._peak = max(self._peak, backend_peak, in_use)
            out["mem/device_bytes_in_use"] = in_use
            out["mem/device_peak_bytes"] = self._peak
        rss = host_rss_bytes()
        if rss is not None:
            out["mem/host_rss_bytes"] = rss
        for name, val in out.items():
            self._reg.gauge(name).set(val)
        return out
