"""Analytical per-program cost accounting: FLOPs + HBM bytes from the
jaxpr (ISSUE 10 tentpole, part 3).

The ROADMAP's standing instruction — "report the MFU ladder every
round" — had no automated source: earlier MFU numbers were computed
by hand from parameter counts.  This module walks
the SAME traced jaxpr the program auditor walks (``program_audit``'s
plumbing, ``engine_program_spec`` for the serving programs) and prices
every equation:

  * ``dot_general`` — 2·B·M·N·K FLOPs from the dimension numbers (the
    number that dominates transformer programs);
  * ``conv_general_dilated`` — 2 · output size · (Cin / groups) ·
    prod(kernel spatial);
  * scatter/gather/slice families — data movement, zero FLOPs;
  * reductions — one FLOP per input element; everything else one FLOP
    per output element;
  * ``scan`` bodies multiply by the trip count (``length``), ``cond``
    branches take the max, ``pjit``/custom-call sub-jaxprs sum.

HBM bytes are the analytical per-eqn traffic (input + output bytes at
the ACTUAL dtype widths — an int8 operand is priced at one byte, so
quantized programs show their bandwidth win, ISSUE 9) — an upper bound
that ignores XLA fusion, useful for relative comparisons and
roofline-style "is this program FLOP- or byte-dominated" calls, not as
a profiler replacement.

Published series: ``program_flops_total`` / ``program_hbm_bytes``
gauges (labeled ``program=``) and the measured-window ``mfu`` gauge
(achieved FLOP/s over a configurable peak —
``PADDLE_TPU_PEAK_FLOPS`` env, a per-device-kind table on TPU, a
documented nominal 1e12 on CPU so CI MFU is a stable relative number).
``tools/serve_bench.py`` / ``tools/train_bench.py`` quote all three in
their JSON lines, so every future BENCH round carries the MFU ladder
for free.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Optional, Tuple

import numpy as np
import jax
from jax.extend.core import ClosedJaxpr

from .program_audit import _aval_of, _nbytes, _subjaxprs_of

__all__ = [
    "CostEstimate", "estimate_jaxpr", "estimate_callable",
    "estimate_engine", "peak_flops", "peak_source", "record_mfu",
    "publish_engine_cost", "PEAK_FLOPS_BY_DEVICE",
]

#: dense bf16 peak FLOP/s per chip by TPU device kind (public spec
#: numbers; matched by prefix against ``jax.devices()[0].device_kind``)
PEAK_FLOPS_BY_DEVICE: Dict[str, float] = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}

#: the CPU-CI nominal peak: an arbitrary but FIXED reference (1 TFLOP/s)
#: so MFU on the CPU lanes is a stable relative number across rounds —
#: absolute MFU claims only mean anything on real hardware peaks
DEFAULT_PEAK_FLOPS = 1.0e12

# primitives that are pure data movement: bytes, no arithmetic
_MOVEMENT_PRIMS = frozenset({
    "gather", "scatter", "scatter-add", "scatter_add", "scatter-mul",
    "scatter-min", "scatter-max", "dynamic_slice",
    "dynamic_update_slice", "slice", "concatenate", "reshape",
    "transpose", "broadcast_in_dim", "squeeze", "rev", "pad",
    "convert_element_type", "bitcast_convert_type", "copy", "iota",
    "select_n", "split", "device_put",
})

# reductions: one FLOP per INPUT element (the output is tiny)
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "reduce",
    "cumsum", "cumprod", "cummax", "cummin",
})


@dataclasses.dataclass
class CostEstimate:
    """One program's analytical cost: total FLOPs, total HBM bytes, and
    the per-primitive breakdown (``{prim: (flops, bytes)}``)."""

    name: str
    flops: float
    hbm_bytes: float
    by_primitive: Dict[str, Tuple[float, float]]

    def to_dict(self) -> dict:
        return {
            "program": self.name,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "by_primitive": {
                k: {"flops": f, "bytes": b}
                for k, (f, b) in sorted(self.by_primitive.items())},
        }

    def publish(self) -> None:
        """Land the totals in the monitor registry next to the runtime
        series they predict."""
        from .. import monitor
        monitor.gauge(
            "program_flops_total",
            "analytical FLOPs per dispatch of a compiled program "
            "(analysis.cost jaxpr walk)",
            ("program",)).set(self.flops, program=self.name)
        monitor.gauge(
            "program_hbm_bytes",
            "analytical HBM bytes per dispatch of a compiled program "
            "(per-eqn input+output traffic at actual dtype widths; "
            "fusion-blind upper bound)",
            ("program",)).set(self.hbm_bytes, program=self.name)

    def __repr__(self) -> str:
        return (f"<CostEstimate {self.name!r} flops={self.flops:.3g} "
                f"hbm_bytes={self.hbm_bytes:.3g}>")


# ---------------------------------------------------------------- pricing
def _closed_of(j):
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def _avals(vars_):
    out = []
    for v in vars_:
        a = _aval_of(v)
        if a is not None and getattr(a, "shape", None) is not None:
            out.append(a)
    return out


def _size(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64)) or 1.0
    except Exception:
        return 1.0


def _dot_general_flops(eqn) -> float:
    """2·B·M·N·K from the dimension numbers — multiply-add pairs
    counted as 2 FLOPs, the MFU convention."""
    lhs, rhs = _avals(eqn.invars)[:2]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = math.prod(int(lhs.shape[d]) for d in lb) or 1
    k = math.prod(int(lhs.shape[d]) for d in lc) or 1
    m = math.prod(int(s) for d, s in enumerate(lhs.shape)
                  if d not in tuple(lc) + tuple(lb)) or 1
    n = math.prod(int(s) for d, s in enumerate(rhs.shape)
                  if d not in tuple(rc) + tuple(rb)) or 1
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    _lhs, rhs = _avals(eqn.invars)[:2]
    out = _avals(eqn.outvars)[0]
    dn = eqn.params.get("dimension_numbers")
    if dn is not None:
        # rhs layout from the dimension numbers; the kernel's in-channel
        # dim is already per-group, so groups need no extra divide
        rhs_spec = dn.rhs_spec
        kernel_spatial = math.prod(
            int(rhs.shape[d]) for d in rhs_spec[2:]) or 1
        cin_per_group = int(rhs.shape[rhs_spec[1]])
    else:
        kernel_spatial = math.prod(int(s) for s in rhs.shape[2:]) or 1
        cin_per_group = int(rhs.shape[1]) if len(rhs.shape) > 1 else 1
    return 2.0 * _size(out) * cin_per_group * kernel_spatial


def _leaf_cost(eqn) -> Tuple[float, float]:
    """(flops, bytes) for one primitive with no sub-jaxprs."""
    name = eqn.primitive.name
    in_avals = _avals(eqn.invars)
    out_avals = _avals(eqn.outvars)
    nbytes = float(sum(_nbytes(a) for a in in_avals)
                   + sum(_nbytes(a) for a in out_avals))
    if name == "dot_general":
        return _dot_general_flops(eqn), nbytes
    if name == "conv_general_dilated":
        return _conv_flops(eqn), nbytes
    if name in _MOVEMENT_PRIMS:
        return 0.0, nbytes
    if name in _REDUCE_PRIMS:
        return float(sum(_size(a) for a in in_avals)) or 1.0, nbytes
    # default: elementwise — one FLOP per output element
    return float(max((_size(a) for a in out_avals), default=0.0)), nbytes


def _jaxpr_cost(jaxpr, by_prim: Dict[str, Tuple[float, float]],
                scale: float = 1.0) -> Tuple[float, float]:
    """Recursive walk: leaf primitives priced by the rules above;
    control flow weighted (scan × trip count, cond = max branch)."""
    flops = 0.0
    nbytes = 0.0

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            body = _closed_of(eqn.params["jaxpr"])
            trips = float(eqn.params.get("length", 1) or 1)
            f, b = _jaxpr_cost(body, by_prim, scale * trips)
            flops += f
            nbytes += b
            continue
        if name == "cond":
            branches = [_closed_of(br)
                        for br in eqn.params.get("branches", ())]
            if branches:
                costs = []
                for br in branches:
                    probe: Dict[str, Tuple[float, float]] = {}
                    costs.append((_jaxpr_cost(br, probe, 1.0), probe))
                (f, b), probe = max(costs, key=lambda c: c[0][0])
                for k, (pf, pb) in probe.items():
                    of, ob = by_prim.get(k, (0.0, 0.0))
                    by_prim[k] = (of + pf * scale, ob + pb * scale)
                flops += f * scale
                nbytes += b * scale
                continue
        if name in ("remat2", "remat", "checkpoint"):
            # remat bodies (ISSUE 11 satellite): the differentiated
            # remat eqn carries BOTH the recompute forward and the
            # backward in one sub-jaxpr — price it fully, or remat'd
            # training programs are underpriced by the whole recompute
            # (FLOPs and HBM both)
            f, b = _jaxpr_cost(_closed_of(eqn.params["jaxpr"]),
                               by_prim, scale)
            flops += f
            nbytes += b
            continue
        if name.startswith("custom_vjp_call") or \
                name.startswith("custom_jvp_call"):
            # custom-derivative wrappers: ONLY the traced primal body
            # (fun_jaxpr/call_jaxpr) is priced — the fwd/bwd entries in
            # params are thunks, not jaxprs, and blindly walking every
            # param would double-count when a version materializes both
            key = next((k for k in ("fun_jaxpr", "call_jaxpr", "jaxpr")
                        if k in eqn.params), None)
            if key is not None:
                f, b = _jaxpr_cost(_closed_of(eqn.params[key]),
                                   by_prim, scale)
                flops += f
                nbytes += b
                continue
        subs = []
        for val in eqn.params.values():
            subs.extend(_subjaxprs_of(val))
        if subs:
            # pjit / while / shard_map / pallas_call bodies: each
            # sub-jaxpr priced once (a while's unknown trip count
            # is deliberately floored at 1 — documented underestimate)
            for sub in subs:
                f, b = _jaxpr_cost(sub, by_prim, scale)
                flops += f
                nbytes += b
            continue
        f, b = _leaf_cost(eqn)
        flops += f * scale
        nbytes += b * scale
        of, ob = by_prim.get(name, (0.0, 0.0))
        by_prim[name] = (of + f * scale, ob + b * scale)
    return flops, nbytes


# ------------------------------------------------------------ public API
def estimate_jaxpr(closed, name: str = "<jaxpr>",
                   publish: bool = False) -> CostEstimate:
    """Price one ClosedJaxpr (see module docstring for the model)."""
    by_prim: Dict[str, Tuple[float, float]] = {}
    jaxpr = getattr(closed, "jaxpr", closed)
    flops, nbytes = _jaxpr_cost(jaxpr, by_prim)
    est = CostEstimate(name, flops, nbytes, by_prim)
    if publish:
        est.publish()
    return est


def estimate_callable(fn, *example_args, static_argnums=(),
                      name: Optional[str] = None,
                      publish: bool = False) -> CostEstimate:
    """Trace ``fn`` on example args/ShapeDtypeStructs (no device work)
    and price the jaxpr — the front door for anything you would
    ``jax.jit``."""
    static_argnums = (static_argnums,) if isinstance(static_argnums, int) \
        else tuple(static_argnums)
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(
        *example_args)
    return estimate_jaxpr(
        closed, name=name or getattr(fn, "__name__", "<fn>"),
        publish=publish)


def estimate_engine(engine, mode: str = "decode", sample=None,
                    publish: bool = True) -> CostEstimate:
    """Price a ContinuousBatchingEngine's compiled program — the exact
    traced fn + abstract batch ``engine_program_spec`` rebuilds (the
    program_audit plumbing), so the estimate covers the signature
    serving actually dispatches.  ``flops / engine.max_batch`` is the
    per-token decode cost MFU accounting divides through."""
    from .program_audit import engine_program_spec
    fn, _donate, args, meta = engine_program_spec(engine, mode, sample)
    closed = jax.make_jaxpr(fn)(*args)
    return estimate_jaxpr(closed, name=meta["name"], publish=publish)


def by_device_kind(table: Dict[str, float], kind: str, what: str,
                   env_name: Optional[str] = None) -> float:
    """Longest-prefix match of a TPU ``device_kind`` in a peaks table.
    A TPU that is not in the table is an error, never a default: a
    utilization against an assumed peak is not a measurement."""
    hits = [p for p in table if kind.startswith(p)]
    if not hits:
        raise ValueError(
            f"no {what} known for TPU device kind {kind!r}: add it to "
            f"the table with its source"
            + (f", or set {env_name}" if env_name else ""))
    return table[max(hits, key=len)]


def _peak_and_source(default: Optional[float] = None) -> Tuple[float, str]:
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        return float(env), "env"
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return (DEFAULT_PEAK_FLOPS if default is None else default,
                "cpu_nominal")
    return (by_device_kind(PEAK_FLOPS_BY_DEVICE, dev.device_kind,
                           "peak FLOP/s", "PADDLE_TPU_PEAK_FLOPS"),
            f"table:{dev.device_kind}")


def peak_flops(default: Optional[float] = None) -> float:
    """The peak FLOP/s utilization divides by: the
    ``PADDLE_TPU_PEAK_FLOPS`` env var when set; on a TPU the
    per-device-kind table (an unknown kind raises); off the TPU the
    fixed CPU-CI nominal (``DEFAULT_PEAK_FLOPS``), which makes the
    ratio a stable relative number for CPU tests and nothing more."""
    return _peak_and_source(default)[0]


def peak_source() -> str:
    """Where :func:`peak_flops` takes its value from: ``"env"``, the
    table row (``"table:<device kind>"``) or ``"cpu_nominal"`` — printed
    beside every ratio so that a number against the CPU nominal is never
    read as a utilization of a device."""
    return _peak_and_source()[1]


def record_mfu(achieved_flops: float, window_seconds: float,
               peak: Optional[float] = None) -> Optional[float]:
    """Set the measured-window ``mfu`` gauge: analytical FLOPs executed
    in the window over ``peak`` FLOP/s × window.  Returns the value
    (None for an empty window)."""
    from .. import monitor
    g = monitor.gauge(
        "mfu", "achieved FLOP/s over the configured peak in the last "
        "measured window (analysis.cost; peak from "
        "PADDLE_TPU_PEAK_FLOPS / device table / CPU nominal)")
    if window_seconds <= 0:
        return None
    peak = peak_flops() if peak is None else float(peak)
    value = float(achieved_flops) / window_seconds / peak
    g.set(value)
    return value


def publish_engine_cost(engine, mode: str = "decode",
                        peak: Optional[float] = None) -> dict:
    """One-call operator surface (``GET /debug/cost``): price the
    engine's decode program, publish the ``program_*`` gauges, and
    derive a process-lifetime MFU from the monitor's own counters
    (``generated_tokens_total`` × per-token FLOPs over the summed
    ``decode_step_seconds``).  Returns the JSON-able summary; the
    ``spmd`` group (ISSUE 11) carries the tier-3 distributed audit —
    static peak HBM, priced collective bytes/ICI seconds, hazard
    count — and publishes ``program_peak_hbm_bytes`` /
    ``collective_bytes_total`` / ``ici_time_seconds`` alongside.
    The endpoint stays cheap: ONE jaxpr trace serves both tiers (the
    spmd audit carries its CostEstimate), and the HLO tier is off
    (``compiled=False``) — a meshed deployment wanting GSPMD
    collectives runs ``analysis.audit_spmd_engine(engine)`` offline."""
    from .. import monitor
    from .spmd import audit_spmd_engine
    try:
        sa = audit_spmd_engine(engine, mode=mode, compiled=False,
                               publish=True)
        est = sa.cost
        est.publish()
    except Exception:   # noqa: BLE001 — tier 3 never breaks /debug
        sa = None
        est = estimate_engine(engine, mode=mode, publish=True)
    flops_per_token = est.flops / max(1, engine.max_batch)
    reg = monitor.get_registry()
    tokens_m = reg.get("generated_tokens_total")
    dec_m = reg.get("decode_step_seconds")
    tokens = tokens_m.value() if tokens_m is not None else 0.0
    dec_sum, dec_n = dec_m.sum_count() if dec_m is not None else (0.0, 0)
    pk = peak_flops() if peak is None else float(peak)
    mfu = record_mfu(tokens * flops_per_token, dec_sum, peak=pk) \
        if dec_sum > 0 else record_mfu(0.0, 1.0, peak=pk)
    out = {
        "program": est.name,
        "program_flops": est.flops,
        "program_hbm_bytes": est.hbm_bytes,
        "flops_per_token": flops_per_token,
        "generated_tokens": tokens,
        "decode_seconds": dec_sum,
        "decode_steps": dec_n,
        "peak_flops": pk,
        "peak_source": "argument" if peak is not None else peak_source(),
        "mfu": mfu,
    }
    if sa is not None:
        out["spmd"] = {
            "peak_hbm_bytes": sa.peak_hbm_bytes,
            "collective_bytes_total": sa.collective_bytes_total,
            "ici_time_seconds": sa.ici_time_seconds,
            "comm_compute_ratio": sa.comm_compute_ratio,
            "comm_bound": sa.comm_bound,
            "mesh_axes": sa.mesh_axes,
            "collectives": len(sa.collectives),
            "findings": len(sa.findings),
        }
    else:
        out["spmd"] = {"error": "spmd audit unavailable"}
    return out
