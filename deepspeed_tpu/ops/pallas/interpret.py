"""When a Pallas kernel runs in interpreter mode.

One rule for every kernel in this package: the interpreter serves the CPU
backend (tests, the virtual mesh), where no TPU lowering exists; on an
accelerator the kernel is always compiled. Nothing else demotes a kernel to
the interpreter, so a compiled program either contains the kernel
(``tpu_custom_call``) or the call site chose another implementation.
"""

from typing import Optional

import jax


def interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` argument wins; ``None`` takes the default."""
    return interpret_default() if interpret is None else interpret
