"""Wall-clock timers.

Reference: ``SynchronizedWallClockTimer`` (``utils/timer.py:44``) uses CUDA
events per timer; here each ``stop()`` drains XLA's async dispatch once
(``block_until_ready``) so the measured span covers device work, and
``ThroughputTimer`` (``utils/timer.py:199``) reports samples/sec + TFLOPs.
"""

import time
from typing import Dict, List, Optional

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"


# one device sentinel, created on first use and reused: the previous
# implementation issued a fresh jax.device_put H2D transfer on EVERY
# stop(sync=True) — a per-step allocation + transfer just to drain the
# dispatch queue. The chained +0 is what forces the
# queue to retire; the operand can be the same buffer every time.
_SYNC_SENTINEL = None


def _sync():
    global _SYNC_SENTINEL
    try:
        import jax

        for _ in range(2):  # one retry with a fresh sentinel (backend reset)
            if _SYNC_SENTINEL is None:
                _SYNC_SENTINEL = jax.device_put(0)
            try:
                (_SYNC_SENTINEL + 0).block_until_ready()
                return
            except Exception:
                _SYNC_SENTINEL = None
    except Exception:
        pass


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start = 0.0
        self.elapsed_records: List[float] = []

    def start(self, sync: bool = False):
        if sync:
            _sync()
        self._start = time.perf_counter()
        self.started = True

    def stop(self, sync: bool = True, record: bool = True):
        if not self.started:
            return
        if sync:
            _sync()
        dt = time.perf_counter() - self._start
        if record:
            self.elapsed_records.append(dt)
        self.started = False

    def elapsed(self, reset: bool = True) -> float:
        total = sum(self.elapsed_records)
        if reset:
            self.reset()
        return total

    def mean(self) -> float:
        return sum(self.elapsed_records) / max(1, len(self.elapsed_records))

    def reset(self):
        self.elapsed_records = []
        self.started = False


class SynchronizedWallClockTimer:
    """Named-timer registry (reference ``utils/timer.py:44``)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage() -> str:
        from ..accelerator import get_accelerator

        acc = get_accelerator()
        mb = 1024 * 1024
        try:
            return (f"alloc={acc.memory_allocated() / mb:.1f}MB "
                    f"peak={acc.max_memory_allocated() / mb:.1f}MB")
        except Exception:
            return "alloc=? peak=?"

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False):
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        from ..utils.logging import log_dist

        msg = "time (ms) | " + " | ".join(parts)
        if memory_breakdown:
            msg += " | " + self.memory_usage()
        log_dist(msg)


class ThroughputTimer:
    """Samples/sec + TFLOPs estimate (reference ``utils/timer.py:199``)."""

    def __init__(self, batch_size: int, start_step: int = 2,
                 steps_per_output: Optional[int] = None, monitor_memory: bool = False):
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self._start = None

    def start(self):
        self._start = time.perf_counter()

    def stop(self, global_step: bool = True, report_speed: bool = True,
             model_flops: Optional[float] = None):
        if self._start is None:
            return
        _sync()
        dt = time.perf_counter() - self._start
        self._start = None
        if global_step:
            self.global_step_count += 1
        if self.global_step_count <= self.start_step:
            return
        self.total_elapsed_time += dt
        self.step_elapsed_time += dt
        if (report_speed and self.steps_per_output
                and self.global_step_count % self.steps_per_output == 0):
            from ..utils.logging import log_dist

            msg = (f"step={self.global_step_count} "
                   f"samples/sec={self.avg_samples_per_sec():.2f} "
                   f"step_time={dt:.3f}s")
            if model_flops:
                msg += f" TFLOPs={model_flops / dt / 1e12:.2f}"
            log_dist(msg)
            self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self) -> float:
        steps = self.global_step_count - self.start_step
        if steps <= 0 or self.total_elapsed_time == 0:
            return 0.0
        return steps * self.batch_size / self.total_elapsed_time
