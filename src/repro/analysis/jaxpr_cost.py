"""Analytic per-device cost model from the jaxpr (roofline inputs).

Why not XLA cost_analysis?  On the CPU backend, dots lower to custom-calls
whose FLOPs report as ~0, and while-loop bodies are counted once — useless
for 61-layer scanned models.  This walker is exact where it matters:

  - dot_general FLOPs from dimension numbers (2·batch·M·N·K),
  - scan bodies multiplied by trip count,
  - collective bytes per primitive type with ring-time models,
  - a fusion-optimistic HBM byte model: every op's OUTPUT is written once;
    dot/conv/gather additionally read their inputs (elementwise chains are
    assumed producer-fused, matching XLA:TPU behavior).

All shapes inside shard_map are per-device, so results are per-device — the
denominators of the roofline terms.  Used by launch/dryrun.py alongside the
XLA numbers (both are recorded; EXPERIMENTS.md documents the discrepancy).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import numpy as np

# v5e constants (task statement)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

COLLECTIVES = {
    "psum": "all_reduce",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "collective_permute",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
}


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0                     # major-op (fusion-optimistic) HBM
    bytes_all: float = 0.0                 # every op output (upper bound)
    collective_bytes: float = 0.0          # summed local operand sizes
    ici_time: float = 0.0                  # ring-model seconds (single-link)
    ici_right: float = 0.0                 # +1-direction ppermute seconds
    ici_left: float = 0.0                  # -1-direction ppermute seconds
    collective_counts: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    collective_bytes_by_type: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def add(self, other: "Cost", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.bytes_all += other.bytes_all * mult
        self.collective_bytes += other.collective_bytes * mult
        self.ici_time += other.ici_time * mult
        self.ici_right += other.ici_right * mult
        self.ici_left += other.ici_left * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = (
                self.collective_counts.get(k, 0.0) + v * mult)
        for k, v in other.collective_bytes_by_type.items():
            self.collective_bytes_by_type[k] = (
                self.collective_bytes_by_type.get(k, 0.0) + v * mult)


def _nbytes(aval) -> float:
    if not hasattr(aval, "shape"):
        return 0.0
    n = 1
    for d in aval.shape:
        n *= d
    return float(n) * np.dtype(aval.dtype).itemsize


def _size(aval) -> float:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= d
    return float(n)


def _dot_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dn
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = 1.0
    for d in lb:
        batch *= lhs.shape[d]
    contract = 1.0
    for d in lc:
        contract *= lhs.shape[d]
    m = 1.0
    for i, d in enumerate(lhs.shape):
        if i not in lc and i not in lb:
            m *= d
    n = 1.0
    for i, d in enumerate(rhs.shape):
        if i not in rc and i not in rb:
            n *= d
    return 2.0 * batch * m * n * contract


def _axis_prod(axes, axis_sizes: Dict[str, int]) -> int:
    if axes is None:
        return 1
    if isinstance(axes, (str,)):
        return axis_sizes.get(axes, 1)
    n = 1
    for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        n *= axis_sizes.get(a, 1) if isinstance(a, str) else 1
    return n


def _collective_time(kind: str, local_bytes: float, n: int) -> float:
    """Ring-collective seconds on ICI at 50 GB/s/link."""
    if n <= 1:
        return 0.0
    frac = (n - 1) / n
    if kind == "all_reduce":
        return 2.0 * frac * local_bytes / ICI_BW
    if kind == "all_gather":
        # operand is the shard; each link carries (n-1) shards
        return (n - 1) * local_bytes / ICI_BW
    if kind == "reduce_scatter":
        return frac * local_bytes / ICI_BW
    if kind == "all_to_all":
        return frac * local_bytes / ICI_BW
    if kind == "collective_permute":
        return local_bytes / ICI_BW
    return local_bytes / ICI_BW


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    shape = getattr(mesh, "shape", None)
    if isinstance(shape, dict):
        return {str(k): int(v) for k, v in shape.items()}
    names = getattr(mesh, "axis_names", ())
    try:
        sizes = mesh.devices.shape
    except AttributeError:
        sizes = getattr(mesh, "axis_sizes", ())
    return {str(n): int(s) for n, s in zip(names, sizes)}


def _sub_jaxprs(eqn):
    """Every (Closed)Jaxpr hiding in an eqn's params."""
    out = []
    for v in eqn.params.values():
        if hasattr(v, "eqns"):
            out.append(v)
        elif hasattr(v, "jaxpr"):
            out.append(v.jaxpr)
        elif isinstance(v, (tuple, list)):
            for b in v:
                if hasattr(b, "eqns"):
                    out.append(b)
                elif hasattr(b, "jaxpr"):
                    out.append(b.jaxpr)
    return out


def analyze_jaxpr(jaxpr, axis_sizes: Dict[str, int]) -> Cost:
    cost = Cost()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name

        # ---- recursion ------------------------------------------------------
        if prim == "scan":
            sub = eqn.params["jaxpr"]
            inner = analyze_jaxpr(getattr(sub, "jaxpr", sub), axis_sizes)
            cost.add(inner, mult=float(eqn.params["length"]))
            continue
        if prim == "while":
            # bounded whiles only appear via fori_loop in kernels; count once
            sub = eqn.params["body_jaxpr"]
            inner = analyze_jaxpr(getattr(sub, "jaxpr", sub), axis_sizes)
            cost.add(inner)
            continue
        if prim == "cond":
            inners = [analyze_jaxpr(getattr(b, "jaxpr", b), axis_sizes)
                      for b in eqn.params["branches"]]
            if inners:
                cost.add(max(inners, key=lambda c: c.flops + c.bytes))
            continue
        if prim == "shard_map":
            new_axes = dict(axis_sizes)
            new_axes.update(_mesh_axis_sizes(eqn.params.get("mesh")))
            sub = eqn.params.get("jaxpr")
            cost.add(analyze_jaxpr(getattr(sub, "jaxpr", sub), new_axes))
            continue
        subs = _sub_jaxprs(eqn)
        if subs and prim not in COLLECTIVES:
            # jit / remat / custom_vjp_call_jaxpr / closed_call / ...
            for sub in subs:
                cost.add(analyze_jaxpr(sub, axis_sizes))
            continue

        # ---- collectives -----------------------------------------------------
        if prim in COLLECTIVES:
            kind = COLLECTIVES[prim]
            axes = (eqn.params.get("axes") or eqn.params.get("axis_name")
                    or eqn.params.get("axis"))
            n = _axis_prod(axes, axis_sizes)
            b = sum(_nbytes(v.aval) for v in eqn.invars
                    if hasattr(v, "aval") and hasattr(v.aval, "shape"))
            cost.collective_bytes += b
            t = _collective_time(kind, b, n)
            cost.ici_time += t
            # per-direction attribution: counter-rotating rings ride
            # independent full-duplex torus links
            if prim == "ppermute":
                perm = eqn.params.get("perm") or ()
                rightward = bool(perm) and (
                    (perm[0][1] - perm[0][0]) % max(n, 1) == 1)
                if rightward:
                    cost.ici_right += t
                else:
                    cost.ici_left += t
            else:
                cost.ici_right += t
                cost.ici_left += t
            cost.collective_counts[kind] = (
                cost.collective_counts.get(kind, 0) + 1)
            cost.collective_bytes_by_type[kind] = (
                cost.collective_bytes_by_type.get(kind, 0) + b)
            # collectives also touch HBM
            hbm = b + sum(_nbytes(v.aval) for v in eqn.outvars)
            cost.bytes += hbm
            cost.bytes_all += hbm
            continue

        # ---- compute ---------------------------------------------------------
        out_bytes = sum(_nbytes(v.aval) for v in eqn.outvars)
        if prim == "dot_general":
            cost.flops += _dot_flops(eqn)
            b = out_bytes + sum(
                _nbytes(v.aval) for v in eqn.invars if hasattr(v, "aval"))
            cost.bytes += b
            cost.bytes_all += b
        elif prim in ("gather", "dynamic_slice", "take"):
            # touched rows only: approximate by output size both ways
            cost.bytes += 2 * out_bytes
            cost.bytes_all += 2 * out_bytes
        elif prim in ("scatter", "scatter-add", "scatter_add",
                      "dynamic_update_slice"):
            # in-place on TPU (buffer donation): traffic = the UPDATE, not
            # the whole destination buffer
            upd = _nbytes(eqn.invars[1].aval) if len(eqn.invars) > 1 else 0
            cost.bytes += 2 * upd
            cost.bytes_all += 2 * upd
        elif prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_and",
                      "reduce_or", "argmax", "argmin", "reduce_prod"):
            cost.flops += sum(_size(v.aval) for v in eqn.invars
                              if hasattr(v, "aval"))
            cost.bytes += out_bytes     # input assumed fused upstream
            cost.bytes_all += out_bytes
        elif prim in ("cumsum", "cumprod", "cummax", "associative_scan",
                      "cumlogsumexp", "sort"):
            cost.flops += 2 * _size(eqn.outvars[0].aval)
            cost.bytes += 2 * out_bytes
            cost.bytes_all += 2 * out_bytes
        elif prim == "pallas_call":
            ce = eqn.params.get("cost_estimate")
            if ce is not None:
                cost.flops += getattr(ce, "flops", 0) or 0
                cost.bytes += (getattr(ce, "bytes_accessed", 0) or 0)
                cost.bytes_all += (getattr(ce, "bytes_accessed", 0) or 0)
            else:
                cost.bytes += out_bytes
                cost.bytes_all += out_bytes
        else:
            # elementwise & misc: one flop per output element; HBM traffic
            # assumed fused away (major model) but tracked in bytes_all
            cost.flops += _size(eqn.outvars[0].aval) if eqn.outvars else 0
            cost.bytes_all += out_bytes
    return cost


def analyze_fn(fn, *args, axis_sizes: Optional[Dict[str, int]] = None,
               **kwargs) -> Cost:
    """Trace ``fn`` with ShapeDtypeStruct args and analyze."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return analyze_jaxpr(jaxpr.jaxpr, axis_sizes or {})


def roofline_terms(cost: Cost, chips: int = 1) -> Dict[str, float]:
    """The three §Roofline terms, in seconds (costs are already per-device)."""
    compute = cost.flops / PEAK_FLOPS
    memory = cost.bytes / HBM_BW
    collective = cost.collective_bytes / ICI_BW
    # duplex model: opposite ring directions use independent links
    ici_duplex = max(cost.ici_right, cost.ici_left)
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "ici_model_s": cost.ici_time,
        "ici_duplex_s": ici_duplex,
        "dominant": dominant,
        "flops": cost.flops,
        "bytes": cost.bytes,
        "bytes_all": cost.bytes_all,
        "collective_bytes": cost.collective_bytes,
    }
