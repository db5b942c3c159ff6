"""Precision switch of the compiled fabric engine, and the repo's one
``shard_map`` spelling.

This module owns the **x64 guard** for the compiled fabric engine
(:mod:`repro.core.fabric_pallas`): under ``JAX_ENABLE_X64`` on the CPU
backend the pallas engine computes in float64 and is bit-for-bit
identical to the scalar ``ReferenceFabric``; under the float32 default
it is tolerance-gated only.  :func:`x64_enabled` reports the active mode and :func:`x64_mode`
forces one for a scope (the differential tests exercise both).
"""

from __future__ import annotations

import jax


def x64_enabled() -> bool:
    """True when jax computes in float64 (``JAX_ENABLE_X64`` / config).

    This is the pallas engine's precision contract switch: x64 means
    bit-for-bit equality with ``ReferenceFabric``; float32 means results
    are only tolerance-close (~1e-4 relative on arrival times).
    """
    return bool(jax.config.read("jax_enable_x64"))


def x64_mode(enable: bool):
    """Context manager forcing x64 on or off for a scope (jit caches are
    config-keyed, so toggling mid-process is safe)."""
    return jax.enable_x64(enable)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False,
              axis_names=None):
    """``jax.shard_map`` with ``axis_names=None`` meaning every mesh axis
    is manual, and the varying-manual-axes check off by default."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)
