"""Pallas TPU kernels: compiled on a TPU, interpret mode on the CPU.

flash_attention — causal/sliding-window/softcap GQA attention
paged_attention — decode over SEE++ arena pages (paper §IV.A hot path)
wkv6            — RWKV6 recurrence
segment_zero    — loader §IV.B zeroing semantics as a masked store
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a kernel call runs in the Pallas interpreter.

    An explicit ``interpret`` wins.  Otherwise the backend decides: the
    TPU runs the compiled kernel and the CPU (tests, rehearsals) runs the
    interpreter.  Any other backend raises, so a misconfigured machine
    fails loudly instead of serving through the interpreter.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}"
    )
