"""Ask a child process about the default JAX backend.

A chip belongs to one process at a time, so a supervisor that must stay
off JAX — the launcher's elastic rescale hook, ``bench.py``'s ladder parent
— cannot count devices itself without taking the chip from the children it
is about to start. These probes run the question in a short-lived child
with a hard timeout and hand back the answer.

A failed probe is an answer ("nothing reachable"), never a licence to run
somewhere else: no caller re-pins a run to the CPU on it.

The timeout defaults to ``$DSTPU_HEALTH_TIMEOUT`` seconds (180 when unset)
so every probe site is tuned with one variable. A timeout of 0 (or
negative) reports unhealthy immediately without spawning the probe at all.
"""

import os
import subprocess
import sys
from typing import Optional

DEFAULT_TIMEOUT_S = 180.0
TIMEOUT_ENV = "DSTPU_HEALTH_TIMEOUT"


def health_timeout_s(default: float = DEFAULT_TIMEOUT_S) -> float:
    """The probe timeout: ``$DSTPU_HEALTH_TIMEOUT`` when set and parseable,
    else ``default``."""
    raw = os.environ.get(TIMEOUT_ENV)
    if raw is None or raw.strip() == "":
        return float(default)
    try:
        return float(raw)
    except ValueError:
        return float(default)


_PROBE = ("import jax, jax.numpy as jnp;"
          "y = jax.jit(lambda a: a @ a)(jnp.ones((256, 256), jnp.bfloat16));"
          "jax.block_until_ready(y); print('ok')")


def accelerator_healthy(timeout_s: Optional[float] = None) -> bool:
    """Whether the default jax backend completes a tiny jitted matmul within
    the timeout (any platform counts as healthy; only a hang/crash fails).
    ``timeout_s=None`` resolves via :func:`health_timeout_s`; a non-positive
    timeout reports unhealthy without probing (so a 0-second budget cannot
    hang)."""
    t = health_timeout_s() if timeout_s is None else float(timeout_s)
    if t <= 0:
        return False
    try:
        r = subprocess.run([sys.executable, "-c", _PROBE],
                           capture_output=True, text=True, timeout=t)
        return r.returncode == 0 and r.stdout.strip().endswith("ok")
    except subprocess.TimeoutExpired:
        return False


_COUNT_PROBE = "import jax; print(jax.device_count())"


def accelerator_device_count(timeout_s: Optional[float] = None) -> int:
    """Device count of the default backend, probed in a subprocess so the
    CALLER never initializes the backend (a parent that touches the TPU
    holds it exclusively and starves its child processes). 0 on hang/crash
    or a non-positive timeout."""
    t = health_timeout_s() if timeout_s is None else float(timeout_s)
    if t <= 0:
        return 0
    try:
        r = subprocess.run([sys.executable, "-c", _COUNT_PROBE],
                           capture_output=True, text=True, timeout=t)
        if r.returncode != 0:
            return 0
        return int(r.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        return 0
