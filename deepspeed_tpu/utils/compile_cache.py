"""Where JAX's persistent compilation cache lives.

The cache key includes the directory path, so a directory that moves never
hits. ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads the
variable itself — nothing is set in code then); otherwise it is one fixed
directory inside the checkout, shared by every entry point run from it.
"""

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the persistent cache and return its directory. Call
    before the first compile."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def compile_cache_dir() -> Optional[str]:
    """The directory JAX's persistent cache is using right now (None: off)."""
    return jax.config.jax_compilation_cache_dir
