"""Backend-driven interpret-mode selection for the Pallas kernels.

Only the CPU backend has no kernel lowering path — TPU lowers through
Mosaic and GPU through Triton — so ``interpret=None`` resolves to
``jax.default_backend() == "cpu"`` and real accelerators always compile
the kernels. Every kernel keeps an explicit ``interpret=`` argument for
tests, which pin interpret mode on the CPU.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: "bool | None") -> bool:
    """``None`` → the backend default; an explicit bool always wins.

    Called at trace time (interpret is a static arg), so the backend is
    read once per jit cache entry.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
