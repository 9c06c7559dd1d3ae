"""Whether a Pallas kernel runs compiled or in the Pallas interpreter.

Every `pallas_call` in this package takes `interpret: Optional[bool] = None`
and resolves it here, so the choice is made in one place: compiled on a TPU,
interpreted on the CPU (tests and CPU rehearsals), an error anywhere else.
An explicit `interpret=False` on the CPU is how a test compiles a kernel for
a described TPU; an explicit `interpret=True` on a TPU is refused.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    backend = jax.default_backend()
    if interpret is None:
        if backend == "cpu":
            return True
        if backend == "tpu":
            return False
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"the default backend is {backend!r}")
    if interpret and backend == "tpu":
        raise ValueError("interpret=True on a TPU backend would run the "
                         "kernel in the Pallas interpreter")
    return bool(interpret)
