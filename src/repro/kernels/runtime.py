"""Kernel runtime policy shared by every Pallas entry point.

Lives below ops.py so the kernels themselves (sample_fused, sample_sparse,
histogram) can resolve their ``interpret=None`` default without importing
ops (which imports them back).
"""

from __future__ import annotations

import jax

__all__ = ["interpret_default", "resolve_interpret"]


def interpret_default() -> bool:
    """Compile to Mosaic on a TPU, interpret on the CPU, refuse elsewhere.

    The CPU is where the tests run the kernels; any other backend (a GPU,
    or a host whose chip went missing and fell back to something else)
    raises instead of quietly running the slow interpreter.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile only for a TPU (and run interpreted "
        f"on the CPU for tests); the default backend is {backend!r}. Use "
        "impl='xla' here, or pass interpret=True explicitly")


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means "compile on a TPU, interpret on the CPU"."""
    return interpret_default() if interpret is None else bool(interpret)
