"""Pallas TPU portability: compiler params, memory spaces, DMA helpers.

- ``pallas_compiler_params`` filters kwargs to the fields
  ``pltpu.CompilerParams`` accepts instead of exploding on an unknown knob.
- ``interpret=`` defaults: the CPU backend has no Mosaic toolchain, so every
  kernel defaults to interpret mode unless a TPU backend is present;
  ``REPRO_PALLAS_INTERPRET`` overrides in both directions.
"""
from __future__ import annotations

import dataclasses
import inspect
import os
import warnings
from typing import Any, Callable, Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# --------------------------------------------------------------------------
# compiler params
# --------------------------------------------------------------------------
_COMPILER_PARAMS_CLS = pltpu.CompilerParams
_CP_FIELDS = {f.name for f in dataclasses.fields(_COMPILER_PARAMS_CLS)}


def pallas_compiler_params(**kwargs):
    """Build the TPU compiler-params object.

    Knobs (``dimension_semantics``, ``collective_id``,
    ``vmem_limit_bytes``, ...) the class does not know are dropped with a
    warning.
    """
    kept = {k: v for k, v in kwargs.items() if k in _CP_FIELDS}
    dropped = sorted(set(kwargs) - set(kept))
    if dropped:
        warnings.warn(
            f"compat.pallas_compiler_params: {_COMPILER_PARAMS_CLS.__name__} "
            f"on this JAX does not support {dropped}; dropping", stacklevel=2)
    return _COMPILER_PARAMS_CLS(**kept)


# --------------------------------------------------------------------------
# pallas_call with portable defaults
# --------------------------------------------------------------------------
def interpret_default() -> bool:
    """Mosaic lowering needs a TPU toolchain; interpret everywhere else.
    ``REPRO_PALLAS_INTERPRET`` (1/0) force-overrides the backend probe."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


_PALLAS_CALL_PARAMS = frozenset(inspect.signature(pl.pallas_call).parameters)


def pallas_call(kernel: Callable, *, interpret: Optional[bool] = None,
                compiler_params: Any = None, **kwargs):
    """``pl.pallas_call`` with portable defaults.

    - ``interpret=None`` resolves via :func:`interpret_default` so every
      kernel runs on CPU CI without each call site re-implementing the probe.
    - ``compiler_params`` may be a plain dict of knobs; it is routed through
      :func:`pallas_compiler_params` to the installed params class.
    - kwargs ``pl.pallas_call`` does not know are dropped with a warning
      rather than raising.
    """
    if interpret is None:
        interpret = interpret_default()
    if isinstance(compiler_params, dict):
        compiler_params = pallas_compiler_params(**compiler_params)
    if compiler_params is not None:
        kwargs["compiler_params"] = compiler_params
    unsupported = [k for k in kwargs
                   if k not in _PALLAS_CALL_PARAMS and kwargs[k] is not None]
    for k in unsupported:
        warnings.warn(f"compat.pallas_call: pl.pallas_call on this JAX does "
                      f"not support {k!r}; dropping", stacklevel=2)
    kwargs = {k: v for k, v in kwargs.items()
              if k in _PALLAS_CALL_PARAMS and v is not None}
    return pl.pallas_call(kernel, interpret=interpret, **kwargs)


def cost_estimate(*, flops: int, bytes_accessed: int,
                  transcendentals: int = 0):
    """``pl.CostEstimate`` for a kernel's scheduler hint."""
    return pl.CostEstimate(flops=flops, bytes_accessed=bytes_accessed,
                           transcendentals=transcendentals)


# --------------------------------------------------------------------------
# memory spaces & scratch shapes
# --------------------------------------------------------------------------
#: VMEM scratch allocator: ``VMEM(shape, dtype)``.
VMEM = pltpu.VMEM
#: SMEM memory space (BlockSpec ``memory_space=`` and scratch allocator).
SMEM = pltpu.SMEM
#: "ANY" (compiler-placed / HBM) memory space for ``pl.BlockSpec``.
ANY = pl.ANY



# --------------------------------------------------------------------------
# async-copy / semaphore (in-kernel DMA) helpers
# --------------------------------------------------------------------------
def _require(name: str):
    obj = getattr(pltpu, name, None)
    if obj is None:                                  # pragma: no cover
        raise NotImplementedError(
            f"pltpu.{name} is unavailable on this JAX; the fused "
            f"communication kernels need it")
    return obj


SemaphoreType = _require("SemaphoreType")
#: DMA-semaphore scratch spec (``scratch_shapes=`` entry).
DMA_SEM = SemaphoreType.DMA
make_async_copy = _require("make_async_copy")
make_async_remote_copy = _require("make_async_remote_copy")
#: the per-``collective_id`` cross-device barrier semaphore of a kernel.
barrier_semaphore = _require("get_barrier_semaphore")
semaphore_signal = _require("semaphore_signal")
semaphore_wait = _require("semaphore_wait")
#: ``device_id_type=`` value for logical (mesh-coordinate) addressing.
LOGICAL_DEVICE_ID = _require("DeviceIdType").LOGICAL
