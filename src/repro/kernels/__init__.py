"""Pallas TPU kernels of the fused search path (DESIGN.md section 2).

Every kernel entry takes ``interpret: bool | None = None``. ``None`` is
resolved inside the call by :func:`resolve_interpret`: compiled by Mosaic
on a TPU backend, the Pallas interpreter on every other backend (the CPU
test suite). Nothing is resolved at import, so importing this package
never touches an accelerator.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The kernel mode for this call: an explicit bool wins, ``None``
    follows the default backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
