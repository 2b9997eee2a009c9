"""Shared kernel-runtime decision for every Pallas package.

``interpret=None`` (the default everywhere) lets the platform decide:
compiled through Mosaic on a TPU, the Pallas interpreter on every other
backend (the CPU test suite runs every kernel, the paged decode path
included, through the interpreter). ``interpret=True/False`` forces the
mode for one call. Nothing process-wide can turn the chip path into the
interpreter.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """The single interpret-mode decision for all kernel ops wrappers."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
