"""Shared Pallas kernel utilities."""
from __future__ import annotations

from typing import Optional

import jax

LANE = 128                       # TPU vector lane width / MXU tile edge


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode for one kernel trace.

    An explicit bool wins: a test compiles the real kernel for a
    described TPU from a CPU host with `interpret=False`. Otherwise the
    platform decides when the kernel is traced -- never at import, before
    the caller has picked a platform: the CPU runs the Pallas
    interpreter, the TPU runs the compiled Mosaic kernel, and any other
    platform is refused instead of silently interpreting.
    """
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU (compiled) or the CPU "
        f"(interpreted); no path exists for platform {platform!r}")


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
