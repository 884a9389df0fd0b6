"""Pallas kernels of the hot path, one package per namespace, each with
a pure-jnp ``ref.py`` that the tests hold it to.

The two functions below are the only place that decides, from the
platform, how a kernel runs: compiled on a TPU, interpreted elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode.

    ``None`` means: compiled on a TPU, interpreted elsewhere (the only
    mode Pallas has off the TPU). Asking for interpret mode on a TPU is
    an error, so a chip run can never fall back to the interpreter.
    ``False`` is honoured anywhere: it is how a CPU process compiles a
    kernel for a described, unattached TPU."""
    if interpret is None:
        return not on_tpu()
    if interpret and on_tpu():
        raise ValueError("interpret mode was requested on a TPU; "
                         "kernels run compiled there")
    return bool(interpret)


def flat_backend() -> str:
    """Kernel backend of the flat Δ-SGD engine for this platform: the
    Pallas kernel pair on a TPU, the identical jnp math elsewhere."""
    return "pallas" if on_tpu() else "xla"
