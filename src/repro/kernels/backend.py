"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

Every kernel wrapper takes ``interpret=None`` and resolves it here at
trace time, so the same call site compiles the kernel on a TPU backend
and runs the Pallas interpreter on the CPU test backend.  An explicit
``interpret=True`` is for tests on the CPU only: on a TPU backend it
raises instead of silently running the interpreter.  An explicit
``interpret=False`` compiles for the TPU even from a CPU process, which
is how a kernel is compiled for a described (not attached) chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend would run the "
                         "Pallas interpreter instead of the compiled kernel")
    return bool(interpret)
