"""Wall-clock + throughput timers.

Equivalent of reference ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer``:44, ``ThroughputTimer``:199). On TPU,
"synchronized" means block_until_ready on a device array rather than a CUDA
event pair; under jit the engine only times at step granularity to avoid
breaking async dispatch.
"""

import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist

try:
    import psutil
    _PSUTIL = True
except Exception:  # pragma: no cover
    _PSUTIL = False


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start = 0.0
        self._elapsed = 0.0
        self.records: List[float] = []

    def start(self) -> None:
        if self.started:
            raise RuntimeError(f"timer {self.name} already started")
        self._start = time.perf_counter()
        self.started = True

    def stop(self, record: bool = True) -> None:
        if not self.started:
            raise RuntimeError(f"timer {self.name} not started")
        end = time.perf_counter()
        delta = end - self._start
        self._elapsed += delta
        if record:
            self.records.append(delta)
        self.started = False
        # mirror every stop into the trace (no-op while tracing is off)
        from deepspeed_tpu.telemetry import tracer
        tracer.complete(f"timer/{self.name}", self._start, end)

    def reset(self) -> None:
        """Clear ALL accumulated state — elapsed, records, and any
        in-flight start (a reset mid-window must not leave a stale
        ``started`` that makes the next ``start()`` raise)."""
        self.started = False
        self._start = 0.0
        self._elapsed = 0.0
        self.records.clear()

    def elapsed(self, reset: bool = True) -> float:
        """Elapsed time in seconds since last reset (0.0 when the timer
        never ran). A running timer is sampled without losing the window:
        stop(record=False) + immediate restart."""
        if self.started:
            self.stop(record=False)
            self.start()
        value = self._elapsed
        if reset:
            self._elapsed = 0.0
        return value

    def mean(self) -> float:
        """Mean of recorded stop() intervals; 0.0 with no records."""
        return sum(self.records) / len(self.records) if self.records else 0.0


class SynchronizedWallClockTimer:
    """Named timer registry (reference utils/timer.py:44)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def has_timer(self, name: str) -> bool:
        return name in self.timers

    def log(self, names: List[str], normalizer: float = 1.0,
            reset: bool = True, ranks: Optional[List[int]] = None) -> None:
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        if parts:
            log_dist("time (ms) | " + " | ".join(parts), ranks=ranks)

    @staticmethod
    def memory_usage() -> str:
        if not _PSUTIL:
            return "mem: n/a"
        vm = psutil.virtual_memory()
        return f"host mem used: {vm.used / 2**30:.2f} GB ({vm.percent}%)"


class ThroughputTimer:
    """Samples/sec + TFLOPs tracking (reference utils/timer.py:199)."""

    def __init__(self, batch_size: int, start_step: int = 2,
                 steps_per_output: int = 50, monitor_memory: bool = False):
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self._start = 0.0
        self.started = False

    def start(self) -> None:
        self.started = True
        self._start = time.perf_counter()

    def stop(self, global_step: bool = True, report_speed: bool = True,
             sync=None) -> None:
        """``sync`` — a device array (e.g. the step loss) to block on before
        reading the clock. Without it the timer measures only async-dispatch
        latency, not step latency (the round-1 bug: "3519 samples/s" printed
        for a ~1 s/step run)."""
        if not self.started:
            return
        will_report = (self.steps_per_output and
                       (self.global_step_count + 1) % self.steps_per_output == 0)
        if sync is not None and will_report:
            # block only on reporting steps: a per-step sync would stall the
            # async dispatch pipeline (and adds a host round-trip per step)
            import jax
            jax.block_until_ready(sync)
        self.started = False
        if global_step:
            self.global_step_count += 1
        duration = time.perf_counter() - self._start
        if self.global_step_count > self.start_step:
            self.total_elapsed_time += duration
            if report_speed and self.steps_per_output and \
                    self.global_step_count % self.steps_per_output == 0:
                log_dist(
                    f"step={self.global_step_count}, "
                    f"throughput={self.avg_samples_per_sec():.2f} samples/s, "
                    f"latency={duration:.3f} s")

    def avg_samples_per_sec(self) -> float:
        steps = self.global_step_count - self.start_step
        if steps > 0 and self.total_elapsed_time > 0:
            return self.batch_size * steps / self.total_elapsed_time
        return 0.0
