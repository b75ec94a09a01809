"""Tier-3 static analysis: the SPMD auditor (ISSUE 11 tentpole).

Every distributed program in this tree — a ``shard_map`` collective, a
GSPMD-partitioned ``pjit`` train step, a meshed serving program —
compiles to device code whose two scarce resources are ICI bytes and
HBM bytes, and until now neither was knowable before an expensive
(and, at the 8 GiB gate, sometimes *failed*) run.  This module prices
both statically, the same way ``analysis.cost`` made FLOPs/MFU free:

  1. **Collective extraction + pricing.**  Two complementary tiers:

     * the *jaxpr walk* finds explicit collective eqns
       (``psum``/``psum_invariant``/``pmax``/``pmin``, ``all_gather``,
       ``reduce_scatter``, ``ppermute``, ``all_to_all``) inside
       ``shard_map``/``pjit``/``scan`` sub-jaxprs, resolving mesh-axis
       sizes from the enclosing ``shard_map`` mesh and multiplying by
       scan trip counts;
     * the *HLO scan* (``compiled=True``) lowers + AOT-compiles the
       program and parses the optimized module text for the
       ``all-reduce``/``all-gather``/``reduce-scatter``/
       ``collective-permute``/``all-to-all`` ops the GSPMD partitioner
       *inserted* — the only way to see the gradient-sync collectives
       of a ``NamedSharding`` dp program, whose jaxpr contains no
       collective primitive at all.  Nothing executes; compile only.

     Each collective is priced in bytes at the ACTUAL dtype width and
     in analytic ICI seconds from a per-device-kind link-bandwidth
     table (ring-algorithm byte multipliers; see ``price_collective``),
     giving a compute-vs-communication roofline per program — the
     quantities "T3" (arxiv 2401.16677) and "EQuARX" quantify their
     overlap/int8 wins in, priced *before* we build either.

  2. **Peak-HBM live-buffer estimation.**  A buffer-lifetime walk over
     the jaxpr: donated inputs free at last use (donation aliases
     honored via the same shape/dtype matching the program auditor
     uses), non-donated inputs stay resident, sub-jaxprs (scan bodies,
     remat, pjit calls) contribute their internal peak on top of the
     caller's live set.  Publishes ``program_peak_hbm_bytes`` so the
     8 GiB memory-gate verdict is known statically — ``bench.py`` and
     ``tools/train_bench.py`` quote predicted-vs-measured instead of
     just "rejected".  Fusion-blind like the cost model: an upper
     bound for relative comparisons and gate pre-verdicts, not a
     profiler replacement.

  3. **Sharding hazard rules** (``program_audit`` findings format):

     * ``replicated-large-param`` — a large operand left fully
       replicated in a meshed program (every chip stores all of it);
     * ``implicit-reshard`` — a sharding constraint that silently
       moves an operand to a different spec (an unrequested
       all-to-all);
     * ``scan-collective`` — a collective issued per iteration inside
       a ``scan`` body that a bucketed variant would batch (the T3
       motivation, detected at jaxpr level for shard_map programs and
       at HLO level — collectives inside a ``while`` body — for GSPMD
       programs);
     * ``unsharded-kv-pool`` — a meshed serving program whose KV page
       pools ride unsharded (replicated pools cap pool capacity at
       one chip's HBM).

Published series: ``program_peak_hbm_bytes`` / ``collective_bytes_total``
/ ``ici_time_seconds`` gauges (labeled ``program=``).  Surfaces:
``audit_engine``/``TrainStep.audit_fused`` auto-run this tier when a
mesh is present, ``GET /debug/cost`` carries the ``spmd`` group,
``tools/serve_bench.py``/``tools/train_bench.py`` quote it per JSON
line, and ``tools/spmd_audit.py`` is the CLI.
"""
from __future__ import annotations

import dataclasses
import math
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.core import DropVar
from jax.extend.core import Literal

from .cost import _closed_of, by_device_kind
from .program_audit import (Finding, SEVERITY_WARNING,
                            _aval_of, _nbytes, _shape_str, _eqn_location,
                            _subjaxprs_of)

__all__ = [
    "CollectiveCost", "SpmdAudit", "LINK_BANDWIDTH_BY_DEVICE",
    "DEFAULT_LINK_BANDWIDTH", "link_bandwidth", "price_collective",
    "collectives_from_jaxpr", "collectives_from_hlo_text",
    "estimate_peak_hbm", "audit_spmd_jaxpr", "audit_spmd_callable",
    "audit_spmd_engine", "audit_spmd_fused", "mesh_axes_of_args",
]

#: one-directional aggregate ICI bandwidth per chip by TPU device kind
#: (public spec-sheet Gbps figures converted to bytes/s; matched by
#: prefix against ``jax.devices()[0].device_kind``) — the denominator
#: of the analytic collective time.  Override: PADDLE_TPU_ICI_BYTES_PER_S.
LINK_BANDWIDTH_BY_DEVICE: Dict[str, float] = {
    "TPU v2": 62e9,       # 496 Gbps
    "TPU v3": 82e9,       # 656 Gbps
    "TPU v4": 300e9,      # 2400 Gbps
    "TPU v5 lite": 200e9,  # 1600 Gbps
    "TPU v5e": 200e9,
    "TPU v5p": 600e9,     # 4800 Gbps
    "TPU v6 lite": 448e9,  # 3584 Gbps
    "TPU v6e": 448e9,
}

#: the CPU-CI nominal link bandwidth: arbitrary but FIXED (10 GB/s) so
#: analytic ICI seconds on the CPU lanes are stable relative numbers
#: across rounds — absolute claims only mean anything on real ICI
DEFAULT_LINK_BANDWIDTH = 1.0e10

#: jaxpr collective primitive -> canonical collective kind
_JAXPR_COLLECTIVES: Dict[str, str] = {
    "psum": "all_reduce", "psum_invariant": "all_reduce",
    "pmax": "all_reduce", "pmin": "all_reduce",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter", "psum_scatter": "reduce_scatter",
    "ppermute": "ppermute", "pshuffle": "ppermute",
    "all_to_all": "all_to_all",
}

#: HLO op -> canonical collective kind (the names the SPMD partitioner
#: emits into the optimized module text)
_HLO_COLLECTIVES: Dict[str, str] = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "collective-permute": "ppermute",
    "all-to-all": "all_to_all",
}

#: HLO dtype token -> byte width (actual width pricing: an s8 operand
#: is one byte, so int8 collectives show their EQuARX bandwidth win)
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_LARGE_PARAM_BYTES = 1 << 20    # replicated-operand hazard threshold


@dataclasses.dataclass
class CollectiveCost:
    """One priced collective: where it came from (a jaxpr eqn or an
    HLO instruction), how many devices participate, payload bytes at
    actual dtype width, ring-algorithm bytes over the interconnect,
    and the analytic ICI time."""

    kind: str                 # all_reduce / all_gather / reduce_scatter
                              # / ppermute / all_to_all
    op: str                   # the primitive / HLO op name
    axes: Tuple[str, ...]     # mesh axes (jaxpr tier; () for HLO)
    group_size: int           # devices cooperating in one group
    count: float              # executions per program dispatch
                              # (scan trips multiplied in, jaxpr tier)
    payload_bytes: float      # per-device payload, one execution
    ici_bytes: float          # ring-priced bytes over ICI, all
                              # executions (count folded in)
    ici_seconds: float        # ici_bytes / link bandwidth
    path: str = ""
    line: int = 0
    in_scan: bool = False     # fired per-iteration inside scan/while
    source: str = "jaxpr"     # "jaxpr" | "hlo"
    dtype: str = ""           # payload element dtype ("int8", "f32", …)
                              # — the width the EQuARX-style comparison
                              # of quantized vs full-precision
                              # collectives reads off the audit

    @property
    def dtype_width(self) -> int:
        """Payload element bytes; unknown dtypes price as 4 (the same
        fallback the HLO shape parser uses)."""
        w = _HLO_DTYPE_BYTES.get(self.dtype)
        if w is None:
            import numpy as _np
            try:
                w = int(_np.dtype(self.dtype).itemsize)
            except Exception:   # noqa: BLE001 — opaque dtype token
                w = 4
        return w

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        loc = f" [{self.path}:{self.line}]" if self.path else ""
        scan = " (in scan body)" if self.in_scan else ""
        dt = f" {self.dtype}" if self.dtype else ""
        return (f"{self.kind}[{self.op}]{dt} x{self.count:g} "
                f"n={self.group_size}"
                f" payload={self.payload_bytes:.3g}B "
                f"ici={self.ici_bytes:.3g}B/{self.ici_seconds:.3g}s"
                f"{scan}{loc}")


@dataclasses.dataclass
class SpmdAudit:
    """One program's distributed audit: named+priced collectives, the
    compute-vs-communication roofline, the static peak-HBM estimate,
    and the sharding hazard findings."""

    name: str
    mesh_axes: Dict[str, int]
    collectives: List[CollectiveCost]
    collective_bytes_total: float
    ici_time_seconds: float
    compute_flops: float
    compute_seconds: float        # flops / peak (analysis.cost peak)
    comm_compute_ratio: Optional[float]   # ici time over compute time
    peak_hbm_bytes: float
    link_bandwidth: float
    findings: List[Finding]
    #: the analysis.cost CostEstimate of the same trace (compute side
    #: of the roofline) — carried so callers that need FLOPs/HBM too
    #: (publish_engine_cost, the bench lanes) don't re-trace
    cost: Any = None

    @property
    def comm_bound(self) -> bool:
        """True when the analytic roofline says the interconnect, not
        the MXU, sets this program's floor."""
        return self.ici_time_seconds > self.compute_seconds

    @property
    def collective_bytes_f32_equiv(self) -> float:
        """What the SAME collectives would move at f32 width — the
        denominator of the EQuARX-style quantized-collective win.  A
        program whose collectives are already f32 quotes its own total
        (ratio 1); an int8-collective program quotes the bytes its f32
        twin would have moved, so ``f32_equiv / total`` is the priced
        bandwidth reduction, known before the program is built."""
        jaxpr_colls = [c for c in self.collectives if c.source == "jaxpr"]
        src = jaxpr_colls if (jaxpr_colls and
                              len(jaxpr_colls) < len(self.collectives)) \
            else self.collectives
        return float(sum(
            c.ici_bytes * (4.0 / max(1, c.dtype_width)) for c in src))

    def by_kind(self, kind: str) -> List[CollectiveCost]:
        return [c for c in self.collectives if c.kind == kind]

    def to_dict(self) -> dict:
        return {
            "program": self.name,
            "mesh_axes": dict(self.mesh_axes),
            "collectives": [c.to_dict() for c in self.collectives],
            "collective_bytes_total": self.collective_bytes_total,
            "collective_bytes_f32_equiv": self.collective_bytes_f32_equiv,
            "ici_time_seconds": self.ici_time_seconds,
            "compute_flops": self.compute_flops,
            "compute_seconds": self.compute_seconds,
            "comm_compute_ratio": self.comm_compute_ratio,
            "comm_bound": self.comm_bound,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "link_bandwidth": self.link_bandwidth,
            "findings": [f.to_dict() for f in self.findings],
        }

    def report(self) -> str:
        head = (f"spmd audit: {self.name} — "
                f"{len(self.collectives)} collective(s), "
                f"{self.collective_bytes_total:.3g} B over ICI "
                f"({self.ici_time_seconds:.3g} s), "
                f"peak HBM {self.peak_hbm_bytes / (1 << 20):.1f} MiB, "
                f"{'comm' if self.comm_bound else 'compute'}-bound")
        lines = [head]
        equiv = self.collective_bytes_f32_equiv
        if equiv > self.collective_bytes_total * 1.01:
            # quantized collectives present: quote the priced EQuARX
            # win against the f32 twin of the same program
            lines.append(
                f"  quantized collectives: {self.collective_bytes_total:.3g}"
                f" B over ICI vs {equiv:.3g} B at f32 — "
                f"{equiv / max(1.0, self.collective_bytes_total):.2g}x "
                f"fewer bytes")
        lines += [f"  {c}" for c in self.collectives]
        lines += [f"  {f}" for f in self.findings]
        return "\n".join(lines)

    def publish(self) -> None:
        """Land the series in the monitor registry — the same
        ``program=`` labeling the cost gauges use, so dashboards read
        compute and communication off one label set."""
        from .. import monitor
        monitor.gauge(
            "program_peak_hbm_bytes",
            "static peak-HBM live-buffer estimate per compiled program "
            "(analysis.spmd jaxpr lifetime walk; donation honored; "
            "fusion-blind upper bound)",
            ("program",)).set(self.peak_hbm_bytes, program=self.name)
        monitor.gauge(
            "collective_bytes_total",
            "ring-priced bytes over the interconnect per dispatch of a "
            "compiled program (analysis.spmd; actual dtype widths)",
            ("program",)).set(self.collective_bytes_total,
                              program=self.name)
        monitor.gauge(
            "ici_time_seconds",
            "analytic interconnect time per dispatch of a compiled "
            "program (collective_bytes_total over the per-device-kind "
            "link bandwidth; PADDLE_TPU_ICI_BYTES_PER_S overrides)",
            ("program",)).set(self.ici_time_seconds, program=self.name)
        if self.findings:
            # counter increments only — NOT ProgramAudit.publish(),
            # which would also reset audit_last_error_findings for
            # this program label to the spmd findings' error count
            # (always 0: spmd hazards are warnings) and clobber the
            # tier-1 auditor's error gauge
            try:
                c = monitor.counter(
                    "audit_findings_total",
                    "program-auditor findings observed this process",
                    ("program", "rule_id", "severity"))
                for f in self.findings:
                    c.inc(program=self.name, rule_id=f.rule_id,
                          severity=f.severity)
            except Exception:   # noqa: BLE001 — telemetry never fails audits
                pass

    def __repr__(self) -> str:
        return (f"<SpmdAudit {self.name!r} collectives="
                f"{len(self.collectives)} ici_bytes="
                f"{self.collective_bytes_total:.3g} peak_hbm="
                f"{self.peak_hbm_bytes:.3g}>")


# ------------------------------------------------------------- bandwidth
def link_bandwidth(default: Optional[float] = None) -> float:
    """ICI bytes/s the analytic collective time divides by: the
    ``PADDLE_TPU_ICI_BYTES_PER_S`` env var when set; on a TPU the
    per-device-kind table (an unknown kind raises); off the TPU the
    fixed CPU-CI nominal."""
    env = os.environ.get("PADDLE_TPU_ICI_BYTES_PER_S")
    if env:
        return float(env)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return DEFAULT_LINK_BANDWIDTH if default is None else default
    return by_device_kind(LINK_BANDWIDTH_BY_DEVICE, dev.device_kind,
                          "ICI bandwidth", "PADDLE_TPU_ICI_BYTES_PER_S")


def price_collective(kind: str, payload_bytes: float, group_size: int,
                     bandwidth: Optional[float] = None
                     ) -> Tuple[float, float]:
    """(ici_bytes, ici_seconds) for ONE execution of a collective.

    Ring-algorithm per-device byte multipliers over a group of n:

      * all_reduce       2·(n-1)/n · payload   (reduce-scatter +
                                                all-gather halves)
      * all_gather       (n-1)/n · payload     (payload = the FULL
                                                gathered result)
      * reduce_scatter   (n-1)/n · payload     (payload = the full
                                                pre-scatter input)
      * all_to_all       (n-1)/n · payload
      * ppermute         payload               (one hop per device)

    n == 1 prices to zero bytes/seconds — a mesh-of-1 program is free,
    which is exactly what running the CI lane on one CPU device should
    report."""
    n = max(1, int(group_size))
    payload = float(payload_bytes)
    if n == 1:
        return 0.0, 0.0
    if kind == "all_reduce":
        bytes_ici = 2.0 * (n - 1) / n * payload
    elif kind in ("all_gather", "reduce_scatter", "all_to_all"):
        bytes_ici = (n - 1) / n * payload
    else:                                # ppermute and friends: one hop
        bytes_ici = payload
    bw = link_bandwidth() if bandwidth is None else float(bandwidth)
    return bytes_ici, bytes_ici / bw


# -------------------------------------------------- jaxpr-tier extraction
def _mesh_shape(mesh) -> Dict[str, int]:
    """{axis: size} from a Mesh/AbstractMesh, tolerating both APIs."""
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:   # noqa: BLE001
        try:
            return {str(n): int(s) for n, s in
                    zip(mesh.axis_names, mesh.axis_sizes)}
        except Exception:   # noqa: BLE001
            return {}


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _group_size(eqn, mesh_axes: Dict[str, int]) -> int:
    """Devices cooperating in one group of this collective: the product
    of its named axes' sizes (enclosing shard_map mesh), or the
    primitive's own axis_size param when the mesh is unknown."""
    axes = _eqn_axes(eqn)
    if axes and all(a in mesh_axes for a in axes):
        return int(math.prod(mesh_axes[a] for a in axes))
    size = eqn.params.get("axis_size")
    return int(size) if size else 1


def collectives_from_jaxpr(closed, bandwidth: Optional[float] = None
                           ) -> Tuple[List[CollectiveCost],
                                      Dict[str, int]]:
    """Walk a ClosedJaxpr for explicit collective eqns (the shard_map
    tier).  Returns ``(collectives, mesh_axes)`` where mesh_axes is the
    union of every enclosing shard_map mesh seen.  Scan bodies multiply
    the execution count by the trip count and mark ``in_scan``."""
    bw = link_bandwidth() if bandwidth is None else float(bandwidth)
    out: List[CollectiveCost] = []
    seen_axes: Dict[str, int] = {}

    def walk(jaxpr, mesh_axes: Dict[str, int], scale: float,
             in_scan: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _JAXPR_COLLECTIVES:
                kind = _JAXPR_COLLECTIVES[name]
                n = _group_size(eqn, mesh_axes)
                # payload at actual dtype width; all_gather prices the
                # FULL gathered result, reduce_scatter the full input
                priced_vars = (eqn.outvars if kind == "all_gather"
                               else eqn.invars)
                avals = [a for v in priced_vars
                         if (a := _aval_of(v)) is not None]
                payload = float(sum(_nbytes(a) for a in avals))
                dtype = str(getattr(avals[0], "dtype", "")) \
                    if avals else ""
                ici_b, ici_s = price_collective(kind, payload, n, bw)
                path, line = _eqn_location(eqn)
                out.append(CollectiveCost(
                    kind=kind, op=name, axes=_eqn_axes(eqn),
                    group_size=n, count=scale, payload_bytes=payload,
                    ici_bytes=ici_b * scale, ici_seconds=ici_s * scale,
                    path=path, line=line, in_scan=in_scan,
                    source="jaxpr", dtype=dtype))
                continue
            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                inner_axes = dict(mesh_axes)
                if mesh is not None:
                    inner_axes.update(_mesh_shape(mesh))
                    seen_axes.update(_mesh_shape(mesh))
                walk(_closed_of(eqn.params["jaxpr"]), inner_axes, scale,
                     in_scan)
                continue
            if name == "scan":
                trips = float(eqn.params.get("length", 1) or 1)
                walk(_closed_of(eqn.params["jaxpr"]), mesh_axes,
                     scale * trips, True)
                continue
            if name == "while":
                # unknown trip count, floored at 1 (the cost model's
                # documented convention) but still marked as in-scan
                for key in ("body_jaxpr", "cond_jaxpr"):
                    sub = eqn.params.get(key)
                    if sub is not None:
                        walk(_closed_of(sub), mesh_axes, scale, True)
                continue
            for val in eqn.params.values():
                for sub in _subjaxprs_of(val):
                    walk(sub, mesh_axes, scale, in_scan)

    walk(getattr(closed, "jaxpr", closed), {}, 1.0, False)
    return out, seen_axes


# --------------------------------------------------- HLO-tier extraction
# `%x = f32[64,64]{1,0} all-reduce(...)` and the tuple-shaped variants;
# shapes are captured lazily and re-parsed per element below
_HLO_OP_RE = re.compile(
    r"=\s*(?P<shape>\((?:[^()]|\([^()]*\))*\)|\S+?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?P<start>-start)?\(")
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_HLO_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_HLO_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_HLO_METADATA_RE = re.compile(
    r'metadata=\{[^}]*source_file="([^"]*)"(?:[^}]*source_line=(\d+))?')
_HLO_COMPUTATION_RE = re.compile(r"^\s*(?:ENTRY\s+)?(%?[\w.\-]+)\s*"
                                 r"\(.*->.*\{\s*$")
_HLO_WHILE_BODY_RE = re.compile(r"\bbody=(%?[\w.\-]+)")


def _hlo_element_bytes(shape_text: str) -> List[float]:
    """Per-element byte sizes of an HLO shape string, at actual dtype
    widths; unknown dtypes priced at 4 bytes."""
    out = []
    for dtype, dims in _HLO_SHAPE_RE.findall(shape_text):
        width = _HLO_DTYPE_BYTES.get(dtype)
        if width is None:
            if dtype == "token" or not dtype:
                continue
            width = 4
        size = 1
        for d in dims.split(","):
            if d.strip():
                size *= int(d)
        out.append(float(size * width))
    return out


def _hlo_shape_bytes(shape_text: str, async_start: bool = False) -> float:
    """Payload bytes of an HLO result shape.  Sync ops: tuple elements
    summed (a variadic all-reduce reduces every element).  Async
    ``-start`` ops: the tuple carries the operand alias (and, for
    collective-permute, u32 context scalars) NEXT TO the real result —
    summing would double-count, so the largest element (the gathered /
    reduced output) is the payload."""
    elems = _hlo_element_bytes(shape_text)
    if not elems:
        return 0.0
    return max(elems) if async_start else float(sum(elems))


def _hlo_group_size(line: str, n_devices: int) -> int:
    m = _HLO_GROUPS_IOTA_RE.search(line)
    if m:          # iota form: [groups,group_size]<=[N]
        return int(m.group(2))
    m = _HLO_GROUPS_BRACE_RE.search(line)
    if m:          # brace form: {{0,1,2,...},{...}} — first group's size
        ids = [t for t in m.group(1).replace(" ", "").split(",") if t]
        return max(1, len(ids))
    return max(1, int(n_devices))


def collectives_from_hlo_text(text: str, n_devices: int = 1,
                              bandwidth: Optional[float] = None
                              ) -> List[CollectiveCost]:
    """Parse optimized HLO module text for partitioner-inserted
    collectives — the GSPMD tier.  Each instruction is priced once per
    dispatch of its computation; collectives inside a ``while`` body
    (the fused K-step scan lowers to one) are marked ``in_scan``.
    Counts are per program text, NOT multiplied by while trip counts
    (unknowable at HLO level) — a documented underestimate."""
    bw = link_bandwidth() if bandwidth is None else float(bandwidth)
    # map computation name -> is-a-while-body, from `body=%name` refs
    while_bodies = set(_HLO_WHILE_BODY_RE.findall(text))
    out: List[CollectiveCost] = []
    current_comp = ""
    for line in text.splitlines():
        comp = _HLO_COMPUTATION_RE.match(line)
        if comp:
            current_comp = comp.group(1)
            continue
        m = _HLO_OP_RE.search(line)
        if m:
            op = m.group("op")
            kind = _HLO_COLLECTIVES[op]
            payload = _hlo_shape_bytes(m.group("shape"),
                                       async_start=bool(m.group("start")))
            n = _hlo_group_size(line, n_devices)
            if kind == "reduce_scatter":
                # the instruction's result is the post-scatter SHARD;
                # the priced payload is the full pre-scatter input
                # (matching the jaxpr tier, which prices psum_scatter
                # from its invars)
                payload *= n
            ici_b, ici_s = price_collective(kind, payload, n, bw)
            meta = _HLO_METADATA_RE.search(line)
            path = meta.group(1) if meta else ""
            lineno = int(meta.group(2)) if meta and meta.group(2) else 0
            toks = _HLO_SHAPE_RE.findall(m.group("shape"))
            out.append(CollectiveCost(
                kind=kind, op=op, axes=(), group_size=n, count=1.0,
                payload_bytes=payload, ici_bytes=ici_b,
                ici_seconds=ici_s, path=path, line=lineno,
                in_scan=current_comp in while_bodies, source="hlo",
                dtype=toks[0][0] if toks else ""))
    return out


# ------------------------------------------------------ peak-HBM walk
def _donation_pool(donated_avals) -> List[Tuple[Tuple, int]]:
    pool = []
    for a in donated_avals:
        aval = _aval_of(a)
        if aval is not None and getattr(aval, "shape", None) is not None:
            pool.append(((tuple(aval.shape), str(aval.dtype)),
                         _nbytes(aval)))
    return pool


def _leaf_local_nbytes(leaf) -> Optional[int]:
    """PER-CHIP bytes of a leaf committed to a NamedSharding over a
    >1 mesh — ``prod(shard_shape) * itemsize`` — or None when the leaf
    carries no such placement (replicated-or-unplaced leaves price at
    their global bytes, which IS each chip's cost)."""
    sh = _sharding_of(leaf)
    if sh is None:
        return None
    aval = _aval_of(leaf)
    if aval is None or getattr(aval, "shape", None) is None:
        return None
    try:
        import numpy as _np
        local = sh.shard_shape(tuple(aval.shape))
        return int(math.prod(local)
                   * _np.dtype(aval.dtype).itemsize)
    except Exception:   # noqa: BLE001 — non-divisible / opaque sharding
        return None


def estimate_peak_hbm(closed, donated_avals=(), arg_leaves=()) -> float:
    """Static peak live bytes of one program dispatch: a lifetime walk
    over the jaxpr.  Non-donated inputs (and captured consts) stay
    resident for the whole program (the caller holds them); donated
    inputs free at their last use — the donation alias the compiled
    step exploits.  Intermediates free at last use; sub-jaxpr calls
    (pjit bodies, remat, scan) contribute their own internal peak on
    top of the caller's live set at the call point.

    The estimate is PER-CHIP when shardings are visible (ISSUE 20):
    ``arg_leaves`` (the example args, flattened, positionally matching
    the program invars) lets boundary operands committed to a
    NamedSharding price at their shard bytes — a TP-sharded KV pool
    costs ``global / tp`` per chip — and a ``shard_map`` eqn's outputs
    price at the body's LOCAL outvar bytes rather than the global
    avals the caller sees.  Donation matching stays on global
    shape/dtype (donated_avals are global ShapeDtypeStructs).

    Fusion-blind by construction (XLA fuses elementwise chains whose
    intermediates never materialize), so this is an upper-bound
    estimate: ``predicted >= measured`` is the train_bench assertion,
    and the gate verdict it feeds treats the prediction as the
    pessimistic planner."""
    jaxpr = getattr(closed, "jaxpr", closed)
    donate_pool = _donation_pool(donated_avals)
    local_by_var: Dict[Any, int] = {}
    for v, leaf in zip(getattr(jaxpr, "invars", ()), arg_leaves):
        nb = _leaf_local_nbytes(leaf)
        if nb is not None:
            local_by_var[v] = nb

    def var_bytes(v) -> int:
        a = _aval_of(v)
        return _nbytes(a) if a is not None else 0

    def walk(jpr, freeable_invars: bool) -> Tuple[float, float]:
        """(internal_peak, resident_after) over one jaxpr, counting its
        invars+consts as live on entry.  ``freeable_invars`` controls
        whether invars may be freed at last use (true for sub-jaxprs,
        whose operands are the caller's intermediates; program-level
        invars only free when donated)."""
        live: Dict[Any, int] = {}
        permanent = 0.0

        invars = list(getattr(jpr, "invars", ())) + \
            list(getattr(jpr, "constvars", ()))
        for v in invars:
            nb = local_by_var.get(v, var_bytes(v))
            if freeable_invars:
                live[v] = nb
                continue
            # program boundary: donated inputs are freeable (they land
            # in `live` and die at last use), the rest are resident
            # for the whole dispatch
            key = (tuple(getattr(_aval_of(v), "shape", ()) or ()),
                   str(getattr(_aval_of(v), "dtype", "")))
            hit = next((i for i, (k, _) in enumerate(donate_pool)
                        if k == key), None)
            if hit is not None:
                donate_pool.pop(hit)
                live[v] = nb
            else:
                permanent += nb

        # last-use index over this jaxpr's eqns (outvars never free)
        last_use: Dict[Any, int] = {}
        for i, eqn in enumerate(jpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    last_use[v] = i
        kept = set(v for v in jpr.outvars
                   if not isinstance(v, Literal))

        peak = permanent + sum(live.values())
        for i, eqn in enumerate(jpr.eqns):
            subs = []
            for val in eqn.params.values():
                subs.extend(_subjaxprs_of(val))
            base = permanent + sum(live.values())
            if subs:
                # A sub-jaxpr's internal peak stacks on the caller's
                # live set, minus only the sub invars that ALIAS
                # caller buffers already counted in `base`.  For scan
                # that is the consts+carry prefix — the per-trip xs
                # slices are fresh buffers, and the caller-side
                # operand is the (much larger) STACKED array, so
                # subtracting eqn operand bytes would clamp real body
                # intermediates to zero and break the upper-bound
                # contract (predicted >= measured).
                # a scan's stacked ys accumulators are allocated up
                # front and live through EVERY iteration — they stack
                # with the body peak, not after it
                loop_out_bytes = 0.0
                if eqn.primitive.name in ("scan", "while"):
                    loop_out_bytes = sum(
                        var_bytes(v) for v in eqn.outvars
                        if not isinstance(v, DropVar))
                for sub in subs:
                    sub_invars = list(getattr(sub, "invars", ()))
                    if eqn.primitive.name == "scan":
                        n_alias = (eqn.params.get("num_consts", 0)
                                   + eqn.params.get("num_carry", 0))
                        aliased = sum(var_bytes(v)
                                      for v in sub_invars[:n_alias])
                    else:
                        aliased = sum(var_bytes(v) for v in sub_invars)
                    sub_peak, _ = walk(sub, True)
                    peak = max(peak,
                               base + loop_out_bytes
                               + max(0.0, sub_peak - aliased))
            # a shard_map's outvars carry GLOBAL avals but each chip
            # materializes only its shard — price them at the body's
            # local outvar bytes (per-chip accounting, ISSUE 20)
            if eqn.primitive.name == "shard_map" and subs:
                body = getattr(subs[0], "jaxpr", subs[0])
                for gv, lv in zip(eqn.outvars,
                                  getattr(body, "outvars", ())):
                    if not isinstance(gv, DropVar):
                        local_by_var[gv] = var_bytes(lv)
            # allocate outputs
            for v in eqn.outvars:
                if isinstance(v, DropVar):
                    continue
                live[v] = local_by_var.get(v, var_bytes(v))
            peak = max(peak, permanent + sum(live.values()))
            # free dead intermediates (and donated/freeable inputs)
            for v in eqn.invars:
                if isinstance(v, Literal) or v in kept:
                    continue
                if last_use.get(v) == i:
                    live.pop(v, None)
        return peak, permanent + sum(live.values())

    peak, _ = walk(jaxpr, False)
    return float(peak)


# ------------------------------------------------------- hazard rules
def _spec_is_replicated(sharding) -> Optional[bool]:
    """True/False when ``sharding`` is a NamedSharding over a >1 mesh;
    None when there is no placement to judge."""
    from jax.sharding import NamedSharding
    if not isinstance(sharding, NamedSharding):
        return None
    axes = _mesh_shape(sharding.mesh)
    if math.prod(axes.values() or [1]) <= 1:
        return None
    spec = tuple(getattr(sharding, "spec", ()) or ())
    return all(p is None for p in spec)


def _sharding_of(x):
    sh = getattr(x, "sharding", None)
    from jax.sharding import NamedSharding
    return sh if isinstance(sh, NamedSharding) else None


def mesh_axes_of_args(example_args) -> Dict[str, int]:
    """The union of mesh axes named by the example args' NamedShardings
    — the 'is a mesh present' predicate ``audit_engine``/``audit_fused``
    gate their spmd auto-run on."""
    import jax.tree_util as jtu
    axes: Dict[str, int] = {}
    for leaf in jtu.tree_leaves(tuple(example_args)):
        sh = _sharding_of(leaf)
        if sh is not None:
            axes.update(_mesh_shape(sh.mesh))
    return axes


def _check_replicated_params(arg_leaves, findings: List[Finding],
                             kv_pool_leaves=()) -> None:
    """replicated-large-param + unsharded-kv-pool: large operands whose
    placement replicates them on every chip of a >1 mesh."""
    kv_ids = {id(x) for x in kv_pool_leaves}
    n_param = n_pool = 0
    for leaf in arg_leaves:
        sh = _sharding_of(leaf)
        rep = _spec_is_replicated(sh)
        if rep is not True:
            continue
        aval = _aval_of(leaf)
        if aval is None:
            continue
        nb = _nbytes(aval)
        if nb < _LARGE_PARAM_BYTES:
            continue
        if id(leaf) in kv_ids:
            n_pool += 1
            if n_pool > 4:
                continue
            findings.append(Finding(
                "unsharded-kv-pool", SEVERITY_WARNING,
                f"KV page pool {_shape_str(aval)} ({nb >> 20} MiB) is "
                f"replicated across the mesh — pool capacity is capped "
                f"at one chip's HBM",
                hint="shard the page pools on their leading kv-head "
                     "axis (PartitionSpec('tensor'), what "
                     "PagedKVCache(mesh=...) commits) so pool bytes "
                     "scale with the mesh"))
        else:
            n_param += 1
            if n_param > 8:
                continue
            findings.append(Finding(
                "replicated-large-param", SEVERITY_WARNING,
                f"operand {_shape_str(aval)} ({nb >> 20} MiB) is fully "
                f"replicated in a meshed program — every chip stores "
                f"all of it",
                hint="shard large params over a mesh axis "
                     "(PartitionSpec('tensor', ...)) or accept the "
                     "replication explicitly (dp weights); replicated "
                     "bytes scale HBM cost by the mesh size"))


def _check_implicit_reshard(closed, arg_leaves, findings: List[Finding],
                            bandwidth: float) -> None:
    """implicit-reshard: a sharding_constraint eqn whose target spec
    differs from the operand's declared program-boundary spec — GSPMD
    will materialize the move as an unrequested collective.  Recurses
    into sub-jaxprs (the fused run_steps body lives entirely inside
    the K-step scan eqn), propagating known shardings through call
    boundaries positionally — only onto sub invars whose aval matches
    the caller operand exactly, so a scan's per-trip xs slices (whose
    rank differs from the stacked operand) never inherit a spec that
    would misalign the comparison."""
    jaxpr = getattr(closed, "jaxpr", closed)
    init = {}
    for var, leaf in zip(jaxpr.invars, arg_leaves):
        sh = _sharding_of(leaf)
        if sh is not None:
            init[var] = sh

    def norm(s):
        # normalize trailing Nones so (dp,) == (dp, None)
        s = list(s)
        while s and s[-1] is None:
            s.pop()
        return tuple(s)

    def _same_aval(a, b) -> bool:
        return (a is not None and b is not None
                and tuple(getattr(a, "shape", ()) or ())
                == tuple(getattr(b, "shape", ()) or ())
                and str(getattr(a, "dtype", "")) ==
                str(getattr(b, "dtype", "")))

    n = 0

    def visit(jpr, by_var) -> None:
        nonlocal n
        for eqn in jpr.eqns:
            if eqn.primitive.name == "sharding_constraint":
                var = eqn.invars[0]
                if isinstance(var, Literal):
                    continue
                src = by_var.get(var)
                dst = eqn.params.get("sharding")
                if src is None or dst is None:
                    continue
                try:
                    src_spec = tuple(src.spec)
                    dst_spec = tuple(getattr(dst, "spec", ()) or ())
                except Exception:   # noqa: BLE001 — GSPMDSharding etc.
                    continue
                if norm(src_spec) == norm(dst_spec):
                    continue
                aval = _aval_of(var)
                nb = _nbytes(aval) if aval is not None else 0
                _, secs = price_collective("all_to_all", nb, 2,
                                           bandwidth)
                path, line = _eqn_location(eqn)
                n += 1
                if n > 8:
                    return
                findings.append(Finding(
                    "implicit-reshard", SEVERITY_WARNING,
                    f"operand "
                    f"{_shape_str(aval) if aval is not None else '?'} "
                    f"enters as {src_spec} but is constrained to "
                    f"{dst_spec} — GSPMD moves ~{nb} B cross-device "
                    f"(~{secs:.2g}s ICI) that nobody asked for",
                    hint="make the producer emit the consumer's spec "
                         "(or reshard once, outside the hot program) "
                         "— spec mismatches compile to silent "
                         "all-to-alls",
                    path=path, line=line))
                continue
            subs = []
            for val in eqn.params.values():
                subs.extend(_subjaxprs_of(val))
            if not subs:
                continue
            operands = [v for v in eqn.invars
                        if not isinstance(v, Literal)]
            for sub in subs:
                sub_map = {}
                for sv, ov in zip(getattr(sub, "invars", ()), operands):
                    sh = by_var.get(ov)
                    if sh is not None and _same_aval(_aval_of(sv),
                                                    _aval_of(ov)):
                        sub_map[sv] = sh
                visit(sub, sub_map)

    visit(jaxpr, init)


def _check_scan_collectives(collectives: Sequence[CollectiveCost],
                            findings: List[Finding]) -> None:
    """scan-collective: per-iteration collectives a bucketed variant
    would batch (T3's motivating pattern)."""
    n = 0
    for c in collectives:
        if not c.in_scan or c.group_size <= 1:
            continue
        n += 1
        if n > 8:
            break
        findings.append(Finding(
            "scan-collective", SEVERITY_WARNING,
            f"{c.kind} ({c.payload_bytes:.3g} B over {c.group_size} "
            f"devices) fires on every scan/while iteration "
            f"(x{c.count:g} per dispatch)",
            hint="bucket the payloads and issue one fused collective "
                 "per bucket outside the loop body, or overlap it with "
                 "the backward computation (T3, arxiv 2401.16677)",
            path=c.path, line=c.line))


# ------------------------------------------------------------ public API
def audit_spmd_jaxpr(closed, *, name: str = "<jaxpr>",
                     example_args: Sequence[Any] = (),
                     donated_avals=(), kv_pool_leaves=(),
                     hlo_text: Optional[str] = None,
                     bandwidth: Optional[float] = None,
                     publish: bool = True,
                     _jaxpr_collectives=None) -> SpmdAudit:
    """The assembled tier-3 audit over one traced program: jaxpr-tier
    collectives (+ optional HLO-tier from ``hlo_text``), the peak-HBM
    lifetime walk, hazard rules, and the compute-vs-communication
    roofline (compute seconds from ``analysis.cost`` FLOPs over the
    configured peak).  ``_jaxpr_collectives`` lets callers that
    already walked the jaxpr (the ``compiled`` auto-probe) pass their
    result in instead of paying a second traversal."""
    import jax.tree_util as jtu
    from . import cost as _cost

    bw = link_bandwidth() if bandwidth is None else float(bandwidth)
    collectives, mesh_axes = (_jaxpr_collectives
                              if _jaxpr_collectives is not None
                              else collectives_from_jaxpr(closed, bw))
    arg_leaves = [leaf for leaf in jtu.tree_leaves(tuple(example_args))]
    mesh_axes = dict(mesh_axes)
    mesh_axes.update(mesh_axes_of_args(arg_leaves))
    if hlo_text:
        n_dev = math.prod(mesh_axes.values()) if mesh_axes else 1
        collectives = collectives + collectives_from_hlo_text(
            hlo_text, n_devices=n_dev, bandwidth=bw)

    findings: List[Finding] = []
    meshed = math.prod(mesh_axes.values() or [1]) > 1
    if meshed:
        _check_replicated_params(arg_leaves, findings,
                                 kv_pool_leaves=kv_pool_leaves)
        _check_implicit_reshard(closed, arg_leaves, findings, bw)
    _check_scan_collectives(collectives, findings)

    peak_hbm = estimate_peak_hbm(closed, donated_avals=donated_avals,
                                 arg_leaves=arg_leaves)
    est = _cost.estimate_jaxpr(closed, name=name, publish=False)
    compute_s = est.flops / _cost.peak_flops()
    # totals: when BOTH tiers saw collectives (compiled=True forced on
    # a program with explicit shard_map eqns), the HLO instructions
    # are the lowered form of the SAME jaxpr collectives — totals come
    # from the jaxpr tier alone so nothing is priced twice (the HLO
    # entries stay listed, source="hlo", for inspection).  The
    # compiled=None auto rule never mixes tiers; this guards the
    # explicit override.
    jaxpr_colls = [c for c in collectives if c.source == "jaxpr"]
    totals_src = jaxpr_colls if (jaxpr_colls and
                                 len(jaxpr_colls) < len(collectives)) \
        else collectives
    ici_bytes = float(sum(c.ici_bytes for c in totals_src))
    ici_s = float(sum(c.ici_seconds for c in totals_src))
    audit = SpmdAudit(
        name=name, mesh_axes=mesh_axes, collectives=collectives,
        collective_bytes_total=ici_bytes, ici_time_seconds=ici_s,
        compute_flops=est.flops, compute_seconds=compute_s,
        comm_compute_ratio=(ici_s / compute_s) if compute_s > 0 else None,
        peak_hbm_bytes=peak_hbm, link_bandwidth=bw, findings=findings,
        cost=est)
    if publish:
        try:
            audit.publish()
        except Exception:   # noqa: BLE001 — telemetry never fails audits
            pass
    return audit


def _compiled_hlo_text(fn, example_args, donate_argnums=(),
                       static_argnums=()) -> Optional[str]:
    """Lower + AOT-compile (never execute) and return the optimized
    module text — where the GSPMD partitioner's inserted collectives
    live.  None when the backend can't compile the signature."""
    try:
        jitted = jax.jit(fn, donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
        return jitted.lower(*example_args).compile().as_text()
    except Exception:   # noqa: BLE001 — un-compilable spec: jaxpr tier only
        return None


def audit_spmd_callable(fn, *example_args, donate_argnums=(),
                        static_argnums=(), name: Optional[str] = None,
                        compiled: Optional[bool] = None,
                        kv_pool_leaves=(), bandwidth=None,
                        publish: bool = True) -> SpmdAudit:
    """Trace ``fn`` on example args/ShapeDtypeStructs and run the SPMD
    audit.  ``compiled`` adds the HLO tier (GSPMD-inserted collectives):
    True forces it, False skips it, None (default) auto-enables it when
    the args carry NamedShardings over a >1 mesh AND the jaxpr walk
    found no explicit collective — exactly the GSPMD-partitioned case
    the jaxpr cannot see."""
    import jax.tree_util as jtu
    donate_argnums = (donate_argnums,) if isinstance(donate_argnums, int) \
        else tuple(donate_argnums)
    static_argnums = (static_argnums,) if isinstance(static_argnums, int) \
        else tuple(static_argnums)
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(
        *example_args)
    donated_avals = []
    for i in donate_argnums:
        for leaf in jtu.tree_leaves(example_args[i]):
            aval = _aval_of(leaf)
            if aval is not None:
                donated_avals.append(aval)
    traced_args = [a for i, a in enumerate(example_args)
                   if i not in static_argnums]
    nm = name or getattr(fn, "__name__", "<fn>")

    jx = collectives_from_jaxpr(closed, bandwidth)
    hlo_text = None
    if compiled is None:
        axes = mesh_axes_of_args(jtu.tree_leaves(tuple(traced_args)))
        compiled = (not jx[0]
                    and math.prod(axes.values() or [1]) > 1)
    if compiled:
        hlo_text = _compiled_hlo_text(fn, example_args,
                                      donate_argnums=donate_argnums,
                                      static_argnums=static_argnums)
    return audit_spmd_jaxpr(
        closed, name=nm, example_args=traced_args,
        donated_avals=donated_avals, kv_pool_leaves=kv_pool_leaves,
        hlo_text=hlo_text, bandwidth=bandwidth, publish=publish,
        _jaxpr_collectives=jx)


def audit_spmd_engine(engine, mode: str = "decode", sample=None,
                      compiled: Optional[bool] = None,
                      publish: bool = True) -> SpmdAudit:
    """The SPMD audit of a ContinuousBatchingEngine's compiled program
    — the same ``engine_program_spec`` rebuild the hazard auditor and
    the cost model trace, so all three tiers see one call contract.
    The KV page pools are identified to the unsharded-pool rule."""
    import jax.tree_util as jtu
    from .program_audit import engine_program_spec
    fn, donate, args, meta = engine_program_spec(engine, mode, sample)
    # pools ride as args[-5:-1][0:2] in every mode: (k_pages, v_pages,
    # k_scales, v_scales, wscales) are the trailing five operands
    k_pages, v_pages = args[-5], args[-4]
    pool_leaves = list(k_pages) + list(v_pages)
    donated_avals = []
    for i in donate:
        for leaf in jtu.tree_leaves(args[i]):
            aval = _aval_of(leaf)
            if aval is not None:
                donated_avals.append(aval)
    closed = jax.make_jaxpr(fn)(*args)
    jx = collectives_from_jaxpr(closed)
    hlo_text = None
    axes = mesh_axes_of_args(jtu.tree_leaves(tuple(args)))
    if compiled is None:
        # same auto rule as audit_spmd_callable: compile only when a
        # mesh is present AND the jaxpr walk saw nothing — a program
        # with explicit shard_map collectives must not have the HLO
        # tier re-price them on top (and an engine audit must stay
        # trace-only unless the GSPMD tier is actually needed)
        compiled = (not jx[0]
                    and math.prod(axes.values() or [1]) > 1)
    if compiled:
        hlo_text = _compiled_hlo_text(fn, args, donate_argnums=donate)
    return audit_spmd_jaxpr(
        closed, name=meta["name"], example_args=args,
        donated_avals=donated_avals, kv_pool_leaves=pool_leaves,
        hlo_text=hlo_text, publish=publish, _jaxpr_collectives=jx)


def audit_spmd_fused(train_step, batches, compiled: Optional[bool] = None,
                     publish: bool = True) -> SpmdAudit:
    """The SPMD audit of ``TrainStep.run_steps``'s fused K-step program
    (the ``fused_program_spec`` rebuild): at dp>1 the HLO tier names
    the gradient-sync all-reduces with their priced bytes — the 0.122
    weak-scaling mystery as named instructions."""
    fn, args, donate, static = train_step.fused_program_spec(batches)
    return audit_spmd_callable(
        fn, *args, donate_argnums=donate, static_argnums=static,
        name="TrainStep.run_steps", compiled=compiled, publish=publish)
