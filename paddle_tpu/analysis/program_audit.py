"""Tier-1 static analysis: audit compiled programs at the jaxpr level.

The reference framework's PIR pass stack inspects static programs
*before* they run; the TPU-native analog walks a traced jaxpr.  Every
compiled surface in this tree — ``jax.jit`` callables, ``to_static``
functions, ``static.Program`` replays, the serving engine's
decode/prefill programs — reduces to one jaxpr, so one walker covers
them all.  The hazards it flags are the ones that dominate TPU hot
paths (T3/arxiv 2401.16677: host sync; Ragged Paged Attention/arxiv
2604.15464: layout + transfer discipline):

  * ``host-callback`` — a ``pure_callback``/``io_callback``/debug
    callback inside the program: every step round-trips to Python.
  * ``output-transfer`` — a large un-donated output buffer: it crosses
    the device->host boundary every call (the PR 2 invariant: a decode
    step should ship ``(batch,)`` ids, never ``(batch, vocab)`` logits).
  * ``const-capture`` — a large constant baked into the program instead
    of passed as an argument: re-uploaded per executable and a new
    compile whenever its value changes.
  * ``dtype-promotion`` — f32/f64 values materializing inside a program
    whose working dtype should be narrower (bf16 creep in reverse).
  * ``x64-creep`` — 64-bit avals inside the program (TPU pays double
    bandwidth for them; they only appear with jax_enable_x64).
  * ``missed-donation`` — a large input whose shape/dtype matches an
    output but is not donated: XLA must keep both buffers live.
  * ``weak-type`` / ``nonhashable-static`` — recompilation hazards at
    the call boundary (each weak-typed Python scalar re-specializes;
    a non-hashable static arg cannot hit the jit cache at all).

Findings are structured (rule id, severity, path:line, fix hint),
published to ``paddle_tpu.monitor`` so ``monitor.snapshot()`` carries
the audit result next to the runtime counters it predicts
(``jit_recompile_count`` is the runtime mirror of the recompile rules).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.tree_util as jtu
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "Finding", "ProgramAudit", "audit_jaxpr", "audit_callable",
    "audit_engine", "audit_program", "engine_program_spec",
    "HOST_TRANSFER_RULES",
]

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

# rules that mean "bytes cross the host boundary at run time" — the
# engine decode program must report NONE of these on the sampled path
HOST_TRANSFER_RULES = frozenset({"host-callback", "output-transfer"})

# primitives that re-enter Python from inside the compiled program
_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
})

# default size gates (bytes); callers tune them per program intent
DEFAULT_OUTPUT_TRANSFER_BYTES = 4096
DEFAULT_CONST_BYTES = 1 << 20
DEFAULT_DONATION_BYTES = 1 << 20
_MAX_FINDINGS_PER_RULE = 20


@dataclasses.dataclass
class Finding:
    """One structured audit finding (reference shape: a PIR pass
    diagnostic — rule, location, severity, how to fix)."""

    rule_id: str
    severity: str
    message: str
    hint: str = ""
    path: str = ""
    line: int = 0

    @property
    def location(self) -> str:
        return f"{self.path}:{self.line}" if self.path else "<program>"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        loc = f" [{self.location}]" if self.path else ""
        hint = f"  (fix: {self.hint})" if self.hint else ""
        return f"{self.severity}: {self.rule_id}{loc} {self.message}{hint}"


class ProgramAudit:
    """The result of auditing one program: a named, queryable list of
    findings plus the monitor publication hook."""

    def __init__(self, name: str, findings: Sequence[Finding]):
        self.name = name
        self.findings = list(findings)
        #: the tier-3 distributed audit (analysis.spmd), attached by
        #: audit_engine / TrainStep.audit_fused when a mesh is present
        self.spmd = None

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == SEVERITY_ERROR]

    @property
    def host_transfer_findings(self) -> List[Finding]:
        return [f for f in self.findings
                if f.rule_id in HOST_TRANSFER_RULES]

    def by_rule(self, rule_id: str) -> List[Finding]:
        return [f for f in self.findings if f.rule_id == rule_id]

    def to_dict(self) -> dict:
        return {"program": self.name,
                "findings": [f.to_dict() for f in self.findings]}

    def report(self) -> str:
        head = (f"program audit: {self.name} — "
                f"{len(self.errors)} error(s), "
                f"{len(self.findings) - len(self.errors)} warning(s)")
        return "\n".join([head] + [f"  {f}" for f in self.findings])

    def publish(self) -> None:
        """Feed the findings into ``paddle_tpu.monitor`` so
        ``monitor.snapshot()`` exports them next to runtime metrics."""
        from .. import monitor
        c = monitor.counter(
            "audit_findings_total",
            "program-auditor findings observed this process",
            ("program", "rule_id", "severity"))
        for f in self.findings:
            c.inc(program=self.name, rule_id=f.rule_id,
                  severity=f.severity)
        monitor.gauge(
            "audit_last_error_findings",
            "error-severity findings of the most recent audit per program",
            ("program",)).set(len(self.errors), program=self.name)

    def __repr__(self) -> str:
        return (f"<ProgramAudit {self.name!r} findings="
                f"{len(self.findings)} errors={len(self.errors)}>")


# ---------------------------------------------------------------- helpers
def _aval_of(x) -> Optional[Any]:
    aval = getattr(x, "aval", None)
    if aval is not None:
        return aval
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return x
    return None


def _nbytes(aval) -> int:
    try:
        size = int(np.prod(aval.shape, dtype=np.int64))
        return size * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _shape_str(aval) -> str:
    try:
        return f"{np.dtype(aval.dtype).name}{list(aval.shape)}"
    except Exception:
        return repr(aval)


def _eqn_location(eqn) -> Tuple[str, int]:
    """User path:line from an equation's source info ("", 0 where the
    equation carries no user frame)."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "", 0
    return frame.file_name, int(frame.start_line)


def _walk_eqns(jaxpr) -> Iterable[Any]:
    """Every equation in the jaxpr, recursing into call/control-flow
    sub-jaxprs (pjit bodies, scan/while/cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _subjaxprs_of(val):
                yield from _walk_eqns(sub)


def _subjaxprs_of(val):
    if isinstance(val, ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, Jaxpr):
        return [val]
    if isinstance(val, (tuple, list)):
        out = []
        for v in val:
            out.extend(_subjaxprs_of(v))
        return out
    return []


def _np_dtype(dtype):
    """np.dtype or None for extended dtypes (jax PRNG key avals)."""
    try:
        return np.dtype(dtype)
    except TypeError:
        return None


def _is_wide_float(dtype) -> bool:
    return _np_dtype(dtype) in (np.dtype(np.float32),
                                np.dtype(np.float64))


def _is_64bit(dtype) -> bool:
    return _np_dtype(dtype) in (np.dtype(np.int64), np.dtype(np.uint64),
                                np.dtype(np.float64))


# ----------------------------------------------------------------- checks
def _check_callbacks(jaxpr, findings: List[Finding]) -> None:
    n = 0
    for eqn in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMITIVES or "callback" in name:
            path, line = _eqn_location(eqn)
            n += 1
            if n > _MAX_FINDINGS_PER_RULE:
                break
            findings.append(Finding(
                "host-callback", SEVERITY_ERROR,
                f"'{name}' re-enters Python inside the compiled program "
                f"— a host round-trip on every execution",
                hint="compute on device (lax/jnp) or hoist the callback "
                     "out of the compiled region",
                path=path, line=line))


def _check_consts(closed, findings: List[Finding], const_bytes: int) -> None:
    for c in closed.consts:
        aval = _aval_of(c)
        if aval is None:
            continue
        nb = _nbytes(aval)
        if nb > const_bytes:
            findings.append(Finding(
                "const-capture", SEVERITY_WARNING,
                f"captured constant {_shape_str(aval)} ({nb >> 10} KiB) is "
                f"baked into the program",
                hint="pass it as an argument: baked constants re-upload "
                     "per executable and force a recompile when the value "
                     "changes"))


def _match_and_consume(pool: List[Tuple[Tuple, str]], aval) -> bool:
    key = (tuple(aval.shape), str(aval.dtype))
    for i, (k, _) in enumerate(pool):
        if k == key:
            pool.pop(i)
            return True
    return False


def _check_outputs(closed, findings: List[Finding], donated_avals,
                   output_transfer_bytes: int) -> List[Any]:
    """Flag large outputs that are not aliased to a donated input; the
    leftover (unmatched) outputs feed the donation check."""
    pool = [((tuple(a.shape), str(a.dtype)), "") for a in donated_avals]
    leftover = []
    for var in closed.jaxpr.outvars:
        aval = _aval_of(var)
        if aval is None or getattr(aval, "shape", None) is None:
            continue
        if _match_and_consume(pool, aval):
            continue                      # donated alias: stays on device
        leftover.append(aval)
        nb = _nbytes(aval)
        if nb > output_transfer_bytes:
            findings.append(Finding(
                "output-transfer", SEVERITY_ERROR,
                f"output {_shape_str(aval)} ({nb} B) crosses the "
                f"device->host boundary every call",
                hint="keep reductions/sampling on device and return "
                     "per-row scalars or ids; donate state buffers so "
                     "they alias in place"))
    return leftover


def _check_donation(closed, findings: List[Finding], donated_avals,
                    leftover_out_avals, donation_bytes: int) -> None:
    donated_keys = {(tuple(a.shape), str(a.dtype))
                    for a in donated_avals}
    out_pool = [((tuple(a.shape), str(a.dtype)), "")
                for a in leftover_out_avals]
    for var in closed.jaxpr.invars:
        aval = _aval_of(var)
        if aval is None:
            continue
        nb = _nbytes(aval)
        if nb < donation_bytes:
            continue
        key = (tuple(aval.shape), str(aval.dtype))
        if key in donated_keys:
            continue                       # its twin is already donated
        if _match_and_consume(out_pool, aval):
            findings.append(Finding(
                "missed-donation", SEVERITY_WARNING,
                f"input {_shape_str(aval)} ({nb >> 20} MiB) matches an "
                f"output but is not donated — XLA keeps both buffers live",
                hint="pass donate_argnums for state carried through the "
                     "step (KV pages, optimizer state)"))


#: Source files whose eqns are the quantizer implementation itself —
#: the dynamic-quant absmax chain runs f32 and the s32 accumulator
#: converts to f32 without an int8 invar, so the int8-input test alone
#: misses them.  Kept to the quantizer modules proper: the attention /
#: serving files are NOT listed (their dequant math carries int8
#: inputs), so model-code f32 creep stays visible.
_QUANTIZER_SOURCES = ("/ops/pallas/quant_matmul.py",
                      "/paddle_tpu/quantization/")


def _in_quantizer_source(path: str) -> bool:
    return any(m in path.replace("\\", "/") for m in _QUANTIZER_SOURCES)


def _check_dtype_creep(jaxpr, findings: List[Finding],
                       expect_dtype, quantized: bool = False) -> None:
    """Flag eqns that INTRODUCE a wide dtype (no wide input, wide
    output) inside a program meant to run at a narrower working dtype;
    with x64 enabled, 64-bit introductions are flagged unconditionally.

    ``quantized`` (ISSUE 9): in a QUANTIZED program an eqn whose
    inputs include an INT8 array is the dequant/accumulator math —
    int8 -> f32 casts and s32-accumulated dots are the POINT of the
    int8 format (the accumulation must be wider than the storage), so
    they are exempt from the f32-introduction rule; so are eqns
    LOCATED in the quantizer implementation itself (the dynamic-quant
    absmax runs f32 and the s32 accumulator converts to f32 — neither
    carries an int8 input, but both are the format's sanctioned math,
    and flagging them would eat the per-rule cap and bury a real f32
    leak in model code).  The exemption is scoped to quantized audits
    and never covers the x64 rule: 64-bit lanes are unintended
    whatever the storage format."""
    check_f32 = expect_dtype is not None and np.dtype(expect_dtype) in (
        np.dtype("bfloat16"), np.dtype(np.float16))
    int8 = np.dtype(np.int8)
    seen = set()
    n_per_rule = {"f32": 0, "x64": 0}   # caps are per rule, not shared
    for eqn in _walk_eqns(jaxpr):
        int8_in = quantized and any(
            _np_dtype(a.dtype) == int8
            for v in eqn.invars
            if (a := _aval_of(v)) is not None
            and getattr(a, "dtype", None) is not None)
        in_wide = any(_is_wide_float(a.dtype)
                      for v in eqn.invars
                      if (a := _aval_of(v)) is not None
                      and getattr(a, "dtype", None) is not None)
        in_64 = any(_is_64bit(a.dtype)
                    for v in eqn.invars
                    if (a := _aval_of(v)) is not None
                    and getattr(a, "dtype", None) is not None)
        for var in eqn.outvars:
            aval = _aval_of(var)
            if aval is None or getattr(aval, "dtype", None) is None:
                continue
            path, line = _eqn_location(eqn)
            if check_f32 and _is_wide_float(aval.dtype) and not in_wide \
                    and not int8_in \
                    and not (quantized and path
                             and _in_quantizer_source(path)):
                key = ("f32", eqn.primitive.name, path, line)
                if key in seen or n_per_rule["f32"] >= _MAX_FINDINGS_PER_RULE:
                    continue
                seen.add(key)
                n_per_rule["f32"] += 1
                findings.append(Finding(
                    "dtype-promotion", SEVERITY_WARNING,
                    f"'{eqn.primitive.name}' introduces "
                    f"{np.dtype(aval.dtype).name} into a "
                    f"{np.dtype(expect_dtype).name} program "
                    f"({_shape_str(aval)})",
                    hint="cast accumulations explicitly and keep "
                         "activations at the working dtype; f32 creep "
                         "doubles HBM traffic on TPU",
                    path=path, line=line))
            if _is_64bit(aval.dtype) and not in_64:
                key = ("x64", eqn.primitive.name, path, line)
                if key in seen or n_per_rule["x64"] >= _MAX_FINDINGS_PER_RULE:
                    continue
                seen.add(key)
                n_per_rule["x64"] += 1
                findings.append(Finding(
                    "x64-creep", SEVERITY_WARNING,
                    f"'{eqn.primitive.name}' produces 64-bit "
                    f"{_shape_str(aval)} inside the program",
                    hint="use 32-bit index/accumulator dtypes; TPU pays "
                         "double bandwidth for 64-bit lanes",
                    path=path, line=line))


def _check_quant_consts(closed, findings: List[Finding],
                        scale_lens=None) -> None:
    """Quantized-program certification (ISSUE 9): quantization scales
    must ride as TRACED arguments — a scale baked into the program as a
    constant re-uploads per executable and forces a recompile whenever
    the calibration changes (defeating the one-program-any-calibration
    contract).  Flags captured f32 consts shaped like scales: 1-D
    vectors (per-out-channel weight scales) or 4-D pools with a
    trailing singleton (per-slot KV scale pools).  Rope tables (2-D)
    and scalar epsilons pass.  ``scale_lens`` — the program's actual
    1-D scale-vector lengths (``audit_engine`` derives them from the
    decoder's weight-scale operands) — restricts the 1-D rule to those
    lengths, so legitimate 1-D f32 tables (alibi slopes, an inv_freq
    vector) of other sizes can't false-positive; without it any 1-D
    f32 vector is treated as suspect."""
    n = 0
    for c in closed.consts:
        aval = _aval_of(c)
        if aval is None:
            continue
        dt = _np_dtype(getattr(aval, "dtype", None))
        if dt != np.dtype(np.float32):
            continue
        shape = tuple(getattr(aval, "shape", ()) or ())
        looks_like_scale = (
            (len(shape) == 1 and shape[0] > 1
             and (scale_lens is None or shape[0] in scale_lens))
            or (len(shape) == 4 and shape[-1] == 1))
        if looks_like_scale:
            n += 1
            if n > _MAX_FINDINGS_PER_RULE:
                break
            findings.append(Finding(
                "quant-scale-const", SEVERITY_ERROR,
                f"captured f32 constant {_shape_str(aval)} looks like a "
                f"quantization scale baked into the program",
                hint="pass weight scales / KV scale pools as traced "
                     "arguments (JittedPagedDecoder threads them "
                     "through every program); a baked scale pins the "
                     "executable to one calibration"))


def _check_weak_types(example_leaves, findings: List[Finding]) -> None:
    n = 0
    for leaf in example_leaves:
        aval = _aval_of(leaf)
        weak = getattr(aval, "weak_type", False) or (
            isinstance(leaf, (bool, int, float, complex)))
        if weak:
            n += 1
    if n:
        findings.append(Finding(
            "weak-type", SEVERITY_WARNING,
            f"{n} weak-typed (Python scalar) input(s) — each distinct "
            f"Python type re-specializes the compile cache and can "
            f"silently upcast",
            hint="pass jnp/np arrays with explicit dtypes, or mark true "
                 "configuration values static"))


# ------------------------------------------------------------ public API
def audit_jaxpr(closed, *, name: str = "<jaxpr>", donated_avals=(),
                expect_dtype=None,
                output_transfer_bytes: int = DEFAULT_OUTPUT_TRANSFER_BYTES,
                const_bytes: int = DEFAULT_CONST_BYTES,
                donation_bytes: int = DEFAULT_DONATION_BYTES,
                example_leaves=(), publish: bool = True,
                quantized: bool = False,
                scale_lens=None) -> ProgramAudit:
    """Walk a ClosedJaxpr and return the structured audit.
    ``quantized`` adds the scale-const certification (ISSUE 9);
    ``scale_lens`` narrows its 1-D rule to the program's actual
    scale-vector lengths (see ``_check_quant_consts``)."""
    findings: List[Finding] = []
    _check_callbacks(closed.jaxpr, findings)
    _check_consts(closed, findings, const_bytes)
    leftover = _check_outputs(closed, findings, donated_avals,
                              output_transfer_bytes)
    _check_donation(closed, findings, donated_avals, leftover,
                    donation_bytes)
    _check_dtype_creep(closed.jaxpr, findings, expect_dtype,
                       quantized=quantized)
    if quantized:
        _check_quant_consts(closed, findings, scale_lens=scale_lens)
    _check_weak_types(example_leaves, findings)
    audit = ProgramAudit(name, findings)
    if publish:
        try:
            audit.publish()
        except Exception:
            pass                      # telemetry must never fail an audit
    return audit


def audit_callable(fn, *example_args, donate_argnums=(), static_argnums=(),
                   expect_dtype=None, name: Optional[str] = None,
                   publish: bool = True, quantized: bool = False,
                   scale_lens=None, **limits) -> ProgramAudit:
    """Trace ``fn`` on example args (arrays or ShapeDtypeStructs — no
    device work happens) and audit the resulting jaxpr.  This is the
    front door for auditing anything you would ``jax.jit``; pass the
    same ``donate_argnums``/``static_argnums`` you pass jit so donation
    and recompile checks see the real call contract."""
    donate_argnums = (donate_argnums,) if isinstance(donate_argnums, int) \
        else tuple(donate_argnums)
    static_argnums = (static_argnums,) if isinstance(static_argnums, int) \
        else tuple(static_argnums)
    pre_findings: List[Finding] = []
    for i in static_argnums:
        try:
            hash(example_args[i])
        except TypeError:
            pre_findings.append(Finding(
                "nonhashable-static", SEVERITY_ERROR,
                f"static arg {i} ({type(example_args[i]).__name__}) is "
                f"not hashable — the jit cache cannot key on it",
                hint="use tuples/frozen dataclasses for static "
                     "configuration, never lists/dicts/arrays"))
    if pre_findings:
        # an unhashable static arg also breaks tracing — report the
        # call-boundary finding on its own; jit would fail the same way
        audit = ProgramAudit(name or getattr(fn, "__name__", "<fn>"),
                             pre_findings)
        if publish:
            try:
                audit.publish()
            except Exception:
                pass
        return audit
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(
        *example_args)
    donated_avals = []
    for i in donate_argnums:
        for leaf in jtu.tree_leaves(example_args[i]):
            aval = _aval_of(leaf)
            if aval is not None:
                donated_avals.append(aval)
    example_leaves = [
        leaf for i, a in enumerate(example_args)
        if i not in static_argnums for leaf in jtu.tree_leaves(a)]
    return audit_jaxpr(
        closed, name=name or getattr(fn, "__name__", "<fn>"),
        donated_avals=donated_avals, expect_dtype=expect_dtype,
        example_leaves=example_leaves, publish=publish,
        quantized=quantized, scale_lens=scale_lens, **limits)


def engine_program_spec(engine, mode: str = "decode", sample=None):
    """Rebuild a ContinuousBatchingEngine program's EXACT traced
    function + abstract example args + donation contract, without
    running anything — the shared tracing plumbing under
    :func:`audit_engine` (hazard rules) and ``analysis.cost``'s
    FLOPs/HBM estimator (ISSUE 10), so both see one call contract.

    Returns ``(fn, donate_argnums, example_args, meta)`` where ``meta``
    carries ``name`` / ``batch`` (the program's row count) /
    ``quantized`` / ``scale_lens``."""
    import jax.numpy as jnp
    from ..inference.paged import next_pow2

    if mode not in ("decode", "chunk", "ragged"):
        raise ValueError(f"engine programs are mode='decode', "
                         f"'chunk' or 'ragged', got {mode!r}")
    decoder = engine._decoder
    cache = engine.cache
    if sample is None:
        sample = "greedy" if engine.sample_on_device else False
    # the chunk continuation compiles the "prefix" program (the context
    # length is traced, so prefix-hit suffixes and mid-prompt chunks
    # share one compiled program per bucket shape)
    fn, donate = decoder.program_fn(
        "prefix" if mode == "chunk" else mode, sample)
    # the unified ragged step (ISSUE 17) prices/audits at its WORST
    # serving shape: the full decode batch where every row spans the
    # largest bucket the engine composes — the chunk budget (or the
    # verify block when speculation is the widest row type); a decode-
    # only ragged batch is the same program at S=1
    if mode == "ragged":
        S_ragged = max(
            int(engine.prefill_chunk_tokens or 0),
            (engine.spec_k + 1) if getattr(engine, "_spec", False) else 1,
            1)
        S_ragged = next_pow2(S_ragged)
    # the engine's decode buckets are min(next_pow2(active), max_batch),
    # so max_batch IS the largest program shape serving ever compiles —
    # audit that one, not its power-of-two round-up
    B = engine.max_batch
    W = next_pow2(max(1, -(-engine.max_position // cache.page_size)))
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    def _named_sharding(a):
        # carried so the SPMD tier (ISSUE 11) can see the program's
        # real placements: mesh-presence detection and the replicated-
        # param / unsharded-pool rules key on these (make_jaxpr and
        # the tier-1 rules ignore the field)
        from jax.sharding import NamedSharding
        sh = getattr(a, "sharding", None)
        return sh if isinstance(sh, NamedSharding) else None

    def sds_of(a):
        return sds(tuple(a.shape), a.dtype, sharding=_named_sharding(a))

    params = [sds_of(a) for a in decoder._param_arrays()]
    k_pages = tuple(sds_of(a) for a in cache.k_pages)
    v_pages = tuple(sds_of(a) for a in cache.v_pages)
    # quantized serving (ISSUE 9): the scale pools and per-channel
    # weight scales ride as traced operands — empty tuples otherwise,
    # exactly the call contract the decoder jits
    k_scales = tuple(sds_of(a) for a in cache.k_scales)
    v_scales = tuple(sds_of(a) for a in cache.v_scales)
    wscales = tuple(sds_of(s) for s in decoder._wscale_args())
    pools = (k_pages, v_pages, k_scales, v_scales, wscales)
    quantized = bool(getattr(engine, "quantize", None)
                     or getattr(engine, "kv_quant", None))
    # the 1-D baked-scale rule keys on the program's ACTUAL weight-
    # scale lengths so legitimate 1-D f32 tables of other sizes
    # (alibi slopes, inv_freq) can't false-positive the certification
    scale_lens = frozenset(
        s.shape[0] for s in wscales if len(s.shape) == 1)
    if mode == "chunk":
        # the engine dispatches chunks per request (batch 1) at the
        # configured chunk bucket; fn signature: (params, ids,
        # last_idx, pg, sl, ptabs, plens, sampling, pools, wscales)
        B = 1
        S = next_pow2(int(engine.prefill_chunk_tokens or 64))
        if sample == "draw":
            s_args = (sds((B,), jnp.uint32), sds((B,), i32),
                      sds((B,), jnp.float32), sds((B,), jnp.bool_))
        else:
            s_args = ()
        args = (params, sds((B, S), i32), sds((B,), i32),
                sds((B * S,), i32), sds((B * S,), i32),
                sds((B, W), i32), sds((B,), i32), s_args, *pools)
    elif mode == "ragged":
        # ONE program for the whole mixed step: per-row ctx lengths,
        # span lengths and draft counts all ride traced — fn signature
        # (params, ids, ctx_lens, q_lens, pg, sl, ptabs, nd, sampling,
        # pools, wscales), the _ragged_sampling_args 3-tuple (the draw
        # counter is computed in-program from ctx + span + accept)
        S = S_ragged
        if sample == "draw":
            s_args = (sds((B,), jnp.uint32), sds((B,), jnp.float32),
                      sds((B,), jnp.bool_))
        else:
            s_args = ()
        args = (params, sds((B, S), i32), sds((B,), i32),
                sds((B,), i32), sds((B * S,), i32), sds((B * S,), i32),
                sds((B, W), i32), sds((B,), i32), s_args, *pools)
        if cache.state_pools:
            # a recurrent model's: its slot pools (donated) and (the
            # rows' slots, the rows of several tokens, the page count)
            args += (tuple(sds_of(a) for a in cache.state_pools),
                     (sds((B,), i32), sds((0 if S == 1 else 2,), i32),
                      sds((), i32)))
    else:
        if sample == "draw":
            s_args = (sds((B,), jnp.uint32), sds((B,), i32),
                      sds((B,), jnp.float32), sds((B,), jnp.bool_))
        else:
            s_args = ()
        args = (params, sds((B, 1), i32), sds((B,), i32), sds((B,), i32),
                sds((B,), i32), sds((B,), i32), sds((B, W), i32), s_args,
                *pools)
    meta = {
        "name": f"engine.{mode}"
                f"[{'logits' if sample is False else sample}]",
        "batch": B,
        "quantized": quantized,
        "scale_lens": scale_lens,
    }
    return fn, donate, args, meta


def audit_engine(engine, mode: str = "decode", sample=None,
                 per_row_budget: int = 64, publish: bool = True,
                 **limits) -> ProgramAudit:
    """Audit one of a ContinuousBatchingEngine's compiled programs
    without running it: rebuilds the exact traced function + donation
    contract ``JittedPagedDecoder`` jits and traces it on abstract
    inputs shaped like a full decode batch
    (:func:`engine_program_spec` is the shared rebuild).

    With the engine's default ``sample_on_device=True`` the program's
    only non-donated outputs are the ``(batch,)`` int32 ids (decode) —
    plus the ``(batch,)`` int32 accept counts and the counted walk for
    ``mode="ragged"`` — so the audit must report zero host-transfer
    findings (PR 2's invariant).  On a speculating engine the ragged
    audit spans the verify block (``spec_k + 1`` tokens a row) and also
    proves no ``[B, k]``-shaped draft block was baked in as a constant
    (the block rides as a traced argument) and that BOTH page pools
    stay donated.  A QUANTIZED engine (ISSUE 9: ``quantize``
    and/or ``kv_quant``) is certified further: donation intact on the
    int8 page AND scale pools, int8->accumulator casts exempt from the
    dtype-creep rule, and no scale baked in as a const
    (``quant-scale-const``).  ``mode="chunk"`` audits the CHUNKED-PREFILL
    continuation program (ISSUE 7; shared with the prefix-cache suffix
    path): one chunk's token bucket rides as a traced argument with the
    context length/table traced alongside, so the audit proves the
    chunk loop is transfer-free with donation intact — interleaving
    chunk sizes can never smuggle a host sync into the serving loop.
    ``per_row_budget`` is the allowed host-transfer bytes per batch row
    (ids are 4; ids + accept are 8; a logits row is vocab*4).

    When the program's operands carry NamedShardings over a >1 mesh,
    the tier-3 SPMD audit (``analysis.spmd``) runs automatically: its
    sharding-hazard findings merge into this audit and the full
    distributed audit rides on ``audit.spmd``."""
    fn, donate, args, meta = engine_program_spec(engine, mode, sample)
    limits.setdefault("output_transfer_bytes",
                      meta["batch"] * per_row_budget)
    audit = audit_callable(
        fn, *args, donate_argnums=donate, name=meta["name"],
        publish=publish, quantized=meta["quantized"],
        scale_lens=meta["scale_lens"], **limits)
    try:
        import math as _math
        from .spmd import audit_spmd_engine, mesh_axes_of_args
        axes = mesh_axes_of_args(jtu.tree_leaves(tuple(args)))
        if _math.prod(axes.values() or [1]) > 1:
            audit.spmd = audit_spmd_engine(engine, mode=mode,
                                           sample=sample, publish=publish)
            audit.findings.extend(audit.spmd.findings)
    except Exception:   # noqa: BLE001 — tier 3 must never fail tier 1
        pass
    return audit


def audit_program(program, feed, fetch_list=None, publish: bool = True,
                  **limits) -> ProgramAudit:
    """Audit a ``static.Program``: traces the recorded replay (captured
    eager state surfaces as inputs, exactly as ``Executor.run`` compiles
    it) and walks the jaxpr."""
    closed, example_leaves = program.make_jaxpr(feed, fetch_list)
    return audit_jaxpr(closed, name=f"static.Program[{len(program.ops)} ops]",
                       example_leaves=example_leaves, publish=publish,
                       **limits)
