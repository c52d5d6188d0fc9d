"""Shared example bootstrap (imported for its side effect).

Honors JAX_PLATFORMS even in a process where jax was imported before the
variable was read: re-apply it through jax.config before any device use.
"""

import os

if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
