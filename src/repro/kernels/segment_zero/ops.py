"""Dispatching wrapper for segment_zero."""

from __future__ import annotations

from repro.kernels import resolve_interpret

from .segment_zero import segment_zero_pallas

__all__ = ["segment_zero"]


def segment_zero(x, lo, hi, *, interpret: bool | None = None):
    return segment_zero_pallas(x, lo, hi,
                               interpret=resolve_interpret(interpret))
