"""JAX/Pallas portability layer — the ONLY module allowed to touch JAX's
drift-prone surface.

The repo runs on the installed JAX (0.9.0) and spells each symbol the way
that release does:

  ==============================  ==============================
  JAX symbol                      compat entry point
  ==============================  ==============================
  ``jax.shard_map(check_vma=)``   ``shard_map``
  ``pltpu.CompilerParams``        ``pallas_compiler_params``
  ``lax.axis_size``               ``axis_size``
  ``jax.extend.core.Literal``     ``Literal``
  ==============================  ==============================

Everything else (``pl.BlockSpec``, ``pl.when``, ``pl.ds``, ``lax``
collectives, ...) is imported directly by consumers.

Rule (enforced by ``repro.analysis.lint``, rule ``compat-import``): no
module outside ``repro/compat/`` may reference ``jax.shard_map``,
``jax.core``, ``lax.axis_size`` or ``pltpu.*CompilerParams`` directly —
import through this package instead.
"""
from jax.extend.core import Literal

from repro.compat._version import JAX_VERSION, version_summary
from repro.compat._aot import cost_analysis
from repro.compat._sharding import axis_size, shard_map
from repro.compat._pallas import (ANY, DMA_SEM, SMEM, VMEM,
                                  LOGICAL_DEVICE_ID, SemaphoreType,
                                  barrier_semaphore, cost_estimate,
                                  interpret_default,
                                  make_async_copy, make_async_remote_copy,
                                  pallas_call, pallas_compiler_params,
                                  semaphore_signal, semaphore_wait)

__all__ = [
    "JAX_VERSION", "version_summary", "Literal",
    "shard_map", "axis_size",
    "pallas_call", "pallas_compiler_params", "interpret_default",
    "cost_estimate", "cost_analysis",
    "VMEM", "SMEM", "ANY",
    "SemaphoreType", "DMA_SEM",
    "make_async_copy", "make_async_remote_copy", "LOGICAL_DEVICE_ID",
    "barrier_semaphore", "semaphore_signal", "semaphore_wait",
]
