"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` as given, or, when it is None, whether the default
    backend is the CPU: a TPU call always compiles the kernel, and the CPU
    (where Mosaic cannot run) always interprets it. Callers that compile
    for a described TPU from a CPU host pass ``interpret=False``."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
