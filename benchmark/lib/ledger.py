"""Compile bookkeeping, after ``chip_smoke.CompileLedger`` (which ran on
the v5e in PR 25) but on jax's own monitoring events alone, so it does not
depend on the program's ``compile_monitor``: backend compile requests (a
persistent-cache hit is still a request: a NEW program reached the
backend), their seconds, and persistent-cache requests / hits."""

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLedger:
    """One per process (jax offers no way to unregister a listener)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_REQUEST:
            self.cache_requests += 1
        elif event == _CACHE_HIT:
            self.cache_hits += 1

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += float(seconds)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_requests": self.cache_requests,
                "cache_hits": self.cache_hits}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        d = {k: b[k] - a[k] for k in a}
        d["compile_s"] = round(d["compile_s"], 3)
        d["cache_misses"] = d["cache_requests"] - d["cache_hits"]
        return d
