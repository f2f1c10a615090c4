"""What the default backend can run: one place that asks JAX which
platform it is on, so every kernel and constructor resolves the same way.

Pallas kernels compile through Mosaic only on a TPU; everywhere else they
run in interpret mode.  A TPU runs no float64 Pallas operand and no
complex128 program at all (the TPU compiler aborts the process on c128), so
those requests are refused here, before anything is traced.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

__all__ = ["on_tpu", "resolve_interpret", "check_pallas_dtype"]


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode exactly when the backend is not a TPU.

    ``None`` resolves from the platform.  An explicit ``True`` on a TPU
    raises: the interpreter would stand in for the compiled kernel without
    anyone noticing.
    """
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError(
            "interpret=True on a TPU backend would run the Pallas "
            "interpreter instead of the compiled kernels; leave interpret "
            "unset (it resolves to False on a TPU)")
    return bool(interpret)


def check_pallas_dtype(storage_dtype) -> None:
    """Refuse a 64-bit Pallas storage dtype on a TPU (Mosaic takes none)."""
    if on_tpu() and np.dtype(storage_dtype).itemsize > 4:
        raise ValueError(
            f"use_pallas=True needs float32 storage on a TPU (Mosaic kernels "
            f"take no {np.dtype(storage_dtype)} operands); request "
            f"dtype=float32 (or complex64), or use_pallas=False for the "
            f"XLA path")
