"""Pallas TPU kernels for the paper's hot spots (one package per kernel:
``<name>.py`` kernel, ``ops.py`` public API, ``ref.py`` dense reference).

Every public entry point takes ``interpret=None`` and resolves it with
``resolve_interpret``: compiled on a TPU backend, the Pallas interpreter
elsewhere. Passing ``interpret=True`` is the only way to run the interpreter
on a TPU, so no caller can reach it there by leaving the flag out.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> compile on TPU, interpret on any other backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
