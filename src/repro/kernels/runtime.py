"""Shared Pallas runtime switches.

One resolver for ``REPRO_PALLAS_INTERPRET``, read *per call* rather than
once at import: tests (and the pallas fabric engine) can toggle the
environment variable — or use :func:`force_interpret` — without
reimporting every module that consults it.  Unset, kernels run through
the Pallas interpreter only when JAX's default backend is the CPU; on a
TPU the same call sites emit Mosaic kernels.  ``REPRO_PALLAS_INTERPRET``
set to ``1`` or ``0`` overrides that either way.

Callers must treat the flag as a *static* compilation option: jitted
wrappers pass it as a static argument (or key their trace caches on it)
so flipping the flag selects a different trace instead of silently
reusing a stale one.
"""

from __future__ import annotations

import os
from typing import Optional

_FORCED: Optional[bool] = None


def interpret_mode() -> bool:
    """Resolve the interpret switch now (not at import time)."""
    if _FORCED is not None:
        return _FORCED
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env == "1"
    import jax
    return jax.default_backend() == "cpu"


class force_interpret:
    """Context manager pinning :func:`interpret_mode` for a test block,
    overriding the environment either way."""

    def __init__(self, value: bool):
        self.value = bool(value)
        self._saved: Optional[bool] = None

    def __enter__(self):
        global _FORCED
        self._saved = _FORCED
        _FORCED = self.value
        return self

    def __exit__(self, *exc):
        global _FORCED
        _FORCED = self._saved
        return False
