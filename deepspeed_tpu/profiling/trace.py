"""Hardware trace capture (xplane/perfetto) around training steps.

Reference: DeepSpeed integrates torch.profiler via the ``flops_profiler`` and
monitor hooks; on TPU the native tool is ``jax.profiler`` — the captured
xplane protobuf opens in TensorBoard's profile plugin / Perfetto and shows
per-op device timelines, HBM traffic, and collective overlap (the evidence
trail for e.g. Domino's overlap claim on real hardware).

Usage::

    from deepspeed_tpu.profiling import trace
    with trace.capture("/tmp/tb"):          # or engine-driven below
        engine.train_batch(batch)

    trace.profile_steps(engine, batches, log_dir="/tmp/tb", steps=3)
"""

import contextlib
import os
from typing import Any, Iterable, Optional

import jax


@contextlib.contextmanager
def capture(log_dir: str, *, host_tracer_level: int = 2,
            python_tracer_level: int = 0):
    """Context manager around any block of dispatches. The trace lands in
    ``<log_dir>/plugins/profile/<run>/`` (TensorBoard layout)."""
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    options.python_tracer_level = python_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def profile_steps(engine: Any, batches: Iterable, *, log_dir: str,
                  steps: int = 3, warmup: int = 1) -> str:
    """Run ``warmup`` uncaptured steps (compile outside the trace), then
    capture ``steps`` steps. Returns the log dir."""
    batches = list(batches)
    if not batches:
        raise ValueError("profile_steps needs at least one batch")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    loss = None
    for i in range(warmup):
        loss = engine.train_batch(batches[i % len(batches)])
    if loss is not None:
        float(loss)  # drain so compile noise stays out of the capture
    with capture(log_dir):
        for i in range(steps):
            loss = engine.train_batch(batches[i % len(batches)])
        float(loss)  # the trace must include the real device work
    return log_dir


def annotate(name: str):
    """Named region in the trace (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)


def export_spans(log_dir: str, filename: str = None) -> Optional[str]:
    """Export the telemetry span tracer's host-phase timeline
    (``telemetry/spans.py``) as Chrome-trace JSON into ``log_dir`` — the
    same directory a :func:`capture` writes its device xplane to, so the
    host step phases and the device op timeline open side by side in
    Perfetto. Returns the path, or None when the tracer holds nothing."""
    from ..telemetry.spans import export_chrome, get_tracer

    tr = get_tracer()
    spans = tr.snapshot()
    open_spans = tr.open_spans()
    if not spans and not open_spans:
        return None
    os.makedirs(log_dir, exist_ok=True)
    name = filename or f"spans-{os.getpid()}.trace.json"
    return export_chrome(os.path.join(log_dir, name), spans, open_spans)
