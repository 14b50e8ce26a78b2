"""Pallas TPU kernels for the attention and scan hot spots, plus ``ops``
(the dispatching wrappers the models call) and ``ref`` (the oracles)."""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(requested: Optional[bool] = None) -> bool:
    """Whether a ``pl.pallas_call`` runs in the Pallas interpreter.

    The one place the choice is made.  The kernels are Mosaic TPU kernels:
    a TPU always compiles them, every other backend can only interpret
    them.  ``requested`` lets a caller force compilation (``False``) where
    the platform check cannot see the target — a compile for a described,
    not attached, TPU — but interpretation is never allowed on a TPU.
    """
    on_tpu = jax.default_backend() == "tpu"
    if requested is None:
        return not on_tpu
    if requested and on_tpu:
        raise ValueError("Pallas interpret mode is never used on a TPU")
    return requested
