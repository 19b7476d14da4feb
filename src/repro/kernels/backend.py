"""Backend selection for the Pallas kernels: compile on TPU, interpret
elsewhere.

Every public kernel entry point (kernels/ops.py) takes
``interpret: bool | None`` and resolves it with ``resolve_interpret``
BEFORE entering the kernel's ``jax.jit``, so the static argument the
jit sees is always a concrete bool and the resolved mode is part of the
compile cache key.  Resolution, highest first:

  1. **Per-call argument** — an explicit ``interpret=True/False``.
  2. **Scoped override** — ``with interpret_override(False): ...`` pins
     the mode for every call left at ``None`` in the dynamic extent.
     The chip-compile tests use it to lower whole serving programs for
     a described TPU from a CPU process.
  3. **Backend auto-detect** — interpret unless the active JAX backend
     is a TPU.

This module is import-cycle-free on purpose: the kernel wrappers in
``kernels/ops.py`` import it.
"""

from __future__ import annotations

import contextlib
import threading

import jax

_local = threading.local()


def interpret_default() -> bool:
    """The scoped ``interpret_override`` if set, else True unless the
    active JAX backend is a TPU."""
    override = getattr(_local, "override", None)
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def interpret_override(value: bool | None):
    """Pin interpret mode for kernel calls in this dynamic extent.

    ``True``/``False`` force the mode for every kernel invoked with
    ``interpret=None``; ``None`` restores auto resolution.  Overrides
    nest (innermost wins) and are thread-local.  The override is read
    at trace time: lower a FRESH jitted function inside it, since an
    already-traced one keeps the mode it was traced with.
    """
    prev = getattr(_local, "override", None)
    _local.override = None if value is None else bool(value)
    try:
        yield
    finally:
        _local.override = prev


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if explicitly given, else ``interpret_default()``."""
    return interpret_default() if interpret is None else bool(interpret)
