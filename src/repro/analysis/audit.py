"""Static plan auditor: lower a plan's epoch functions WITHOUT running them
and prove the lowered program matches the access contract the plan declares.

Every backend in this repo carries an implicit access contract — what it
stages, which collectives it issues, which buffers it donates.  PR 7's
``verify_timeline()`` checks that contract dynamically, after a run; this
module checks it statically, before one: each backend's real jit'd epoch
callables (the same ``lru_cache``'d objects :func:`repro.core.experiment
.execute` drives) are lowered from abstract avals built out of the plan's
chunk/batch shapes, and the StableHLO + optimized-HLO text is walked with
the seed's :mod:`repro.launch.hlo_cost` parser.  Rules:

``collectives``  single-host and ``gather`` plans must lower to ZERO
                 collectives (gather reshards at the staging put, outside
                 the epoch program); ``psum`` plans must show the partial-
                 gradient all-reduce inside the batch scan — at least one
                 per batch, counted with loop-trip multipliers — and no
                 other collective kinds.
``donation``     the chunked engines declare ``donate_argnums=(0,)``; the
                 compiled module must actually alias every non-empty solver
                 state leaf (``input_output_alias``), or each epoch pays an
                 alias-broken copy of the state.
``dtypes``       no f64/c128 anywhere in the lowered module — a silent
                 f32→f64 promotion doubles every byte the paper counts.
``callbacks``    no host callbacks (``pure_callback`` & friends lower to
                 ``stablehlo.custom_call`` with an ``xla_python``/
                 ``callback`` target) inside traced code: a hidden host
                 round-trip per batch is exactly the access hazard the
                 paper's thesis forbids.
``cache_keys``   lowering the epoch fn for epoch 1 and epoch 2 must produce
                 byte-identical modules — the recompile-per-epoch hazard.
``h2d_bytes``    entry-parameter bytes of the compiled per-device module
                 must reconcile exactly with the planner's ``AccessStats``
                 byte model (state + staged chunk + schedule indices).

Nothing here executes device code: ``.lower()`` traces, ``.compile()``
runs XLA, and both leave the program un-launched.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from collections import Counter
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core import experiment as expmod
from ..core.experiment import (CSR, GATHER, PSUM, ExecutionPlan,
                               ExperimentSpec, PlanError)
from ..core.solvers import init_state, make_epoch_fn, make_resident_epoch_fn
from ..distributed.sharding import staging_shardings
from ..launch.hlo_cost import HloCostModel, _type_bytes
from ..launch.hlo_analysis import COLLECTIVES, memory_dict

RULES = ("collectives", "donation", "dtypes", "callbacks", "cache_keys",
         "h2d_bytes")
PASS, FAIL, SKIP = "pass", "fail", "skip"

_F64_RE = re.compile(r"\bf64\[|\bc128\[|tensor<[0-9x]*f64>")
_CALLBACK_TARGET_RE = re.compile(
    r'custom_call\s*@([\w\.]+)|custom-call[^\n]*custom_call_target="([^"]+)"')
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+)\s*[,)]")


class AuditError(PlanError):
    """Raised by ``plan(..., audit=True)`` when a rule fails."""


# ---------------------------------------------------------------------------
# report surface
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RuleResult:
    rule: str
    status: str          # pass | fail | skip
    evidence: str

    def as_dict(self) -> Dict[str, str]:
        return {"rule": self.rule, "status": self.status,
                "evidence": self.evidence}


@dataclasses.dataclass
class UnitAudit:
    """One lowered program (an epoch-fn shape specialization) × all rules."""
    unit: str
    results: List[RuleResult]

    @property
    def ok(self) -> bool:
        return all(r.status != FAIL for r in self.results)


@dataclasses.dataclass
class AuditReport:
    """Structured outcome of :func:`audit`: rule × unit → pass/fail/skip.

    ``units`` holds one :class:`UnitAudit` per lowered program — streamed
    backends lower one unit per chunk-shape specialization (K and the
    trailing ``m % K`` remainder), resident backends one whole-epoch unit.
    """
    backend: str
    reduction: Optional[str]
    shards: int
    units: List[UnitAudit]

    @property
    def ok(self) -> bool:
        return all(u.ok for u in self.units)

    def failures(self) -> List[Tuple[str, RuleResult]]:
        return [(u.unit, r) for u in self.units for r in u.results
                if r.status == FAIL]

    def describe(self) -> str:
        lines = [f"audit: backend={self.backend} shards={self.shards}"
                 + (f" reduction={self.reduction}" if self.reduction else "")]
        for u in self.units:
            lines.append(f"  {u.unit}")
            for r in u.results:
                lines.append(f"    [{r.status:>4}] {r.rule:<11} {r.evidence}")
        return "\n".join(lines)

    def to_json(self) -> Dict:
        return {
            "backend": self.backend,
            "reduction": self.reduction,
            "shards": self.shards,
            "ok": self.ok,
            "units": [{"unit": u.unit,
                       "results": [r.as_dict() for r in u.results]}
                      for u in self.units],
        }


# ---------------------------------------------------------------------------
# lowering units
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Unit:
    """One program to lower + the byte/collective model it must match."""
    name: str
    lower: Callable[[int], "jax.stages.Lowered"]   # epoch index -> Lowered
    scan_trips: int              # in-graph batch-loop length
    state_leaf_bytes: List[int]  # per flattened state leaf (replicated)
    data_bytes_global: int       # staged payload, global/host view
    data_bytes_per_device: int   # what the per-device entry must declare
    model_h2d_bytes: int         # what AccessStats books for this staging
    pad_bytes: int               # sharding zero-pad (placement artifact)
    donated: bool                # engine declares donate_argnums=(0,)
    key_bytes: int = 0           # PRNG key param (resident only)
    data_arg_bytes: List[int] = dataclasses.field(default_factory=list)
    # ^ per data aval, per-device view — lets the h2d rule match entry
    #   parameters one-for-one instead of only comparing totals

    @property
    def state_bytes(self) -> int:
        return sum(self.state_leaf_bytes)


def _state_avals(plan_: ExecutionPlan):
    """Solver-state avals via eval_shape — no allocation, exactly the pytree
    ``execute`` feeds the epoch fn."""
    return jax.eval_shape(
        lambda w: init_state(plan_.cfg.solver, w, plan_.num_batches),
        jax.ShapeDtypeStruct((plan_.features,), jnp.float32))


def _shard_tree(tree, sharding):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _leaf_bytes(tree) -> List[int]:
    return [int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(tree)]


def _aval_bytes(aval) -> int:
    return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize


def _per_device_bytes(aval, mesh) -> int:
    """Entry-parameter bytes of this aval in the per-device SPMD program."""
    nbytes = _aval_bytes(aval)
    sharding = getattr(aval, "sharding", None)
    if sharding is None or mesh is None:
        return nbytes
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    div = 1
    for entry in sharding.spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            div *= axis_sizes.get(ax, 1)
    return nbytes // max(div, 1)


def _sds(shape, dtype, sharding=None):
    if sharding is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _streamed_units(plan_: ExecutionPlan) -> List[_Unit]:
    spec, cfg = plan_.spec, plan_.cfg
    problem = spec.problem
    m, n, b = plan_.num_batches, plan_.features, spec.batch_size
    K = plan_.chunk
    # adaptive Schemes run the weighted engine: a trailing (k,) float32
    # weight vector joins the staged payload.  The batch dimension ``b`` of
    # the staged avals is then a BOUND, not the exact row count — variable-
    # size draws are zero-padded back to the static shape, so the lowered
    # shapes (and the H2D bytes DeviceStager books for the padded buffers)
    # still reconcile exactly against these avals
    adaptive = plan_.scheme_obj.adaptive
    fn = (make_epoch_fn(problem, cfg, weighted=True) if adaptive
          else make_epoch_fn(problem, cfg))
    state = _state_avals(plan_)
    sharded = plan_.shards > 1
    mesh = spec.mesh if sharded else None
    units: List[_Unit] = []
    # the driver compiles exactly these shape specializations up front
    for k in sorted({K, m % K} - {0}):
        if plan_.fmt == CSR:
            shapes = [(k, b, plan_.kmax), (k, b, plan_.kmax), (k, b), (k,)]
            dtypes = [jnp.int32, jnp.float32, jnp.float32, jnp.int32]
        else:
            shapes = [(k, b, n), (k, b), (k,)]
            dtypes = [jnp.float32, jnp.float32, jnp.int32]
        if adaptive:
            shapes.append((k,))
            dtypes.append(jnp.float32)
        if sharded:
            batch_axes = ((None, "batch", None), (None, "batch"), (None,))
            if plan_.reduction == GATHER:
                # the staging put reshards to replicated BEFORE the jit
                # boundary: the epoch program sees replicated inputs
                rep = NamedSharding(mesh, PartitionSpec())
                shardings = [rep] * len(shapes)
            else:
                shardings = list(staging_shardings(mesh, batch_axes, shapes))
            st = _shard_tree(state, NamedSharding(mesh, PartitionSpec()))
        else:
            shardings = [None] * len(shapes)
            st = state
        data = tuple(_sds(s, d, sh)
                     for s, d, sh in zip(shapes, dtypes, shardings))
        data_global = sum(_aval_bytes(a) for a in data)
        data_arg = [_per_device_bytes(a, mesh) for a in data]
        data_per_dev = sum(data_arg)

        def lower(epoch: int, fn=fn, st=st, data=data):
            del epoch   # shapes are epoch-invariant by construction
            return fn.lower(st, *data)

        units.append(_Unit(
            name=f"epoch_chunk[k={k}]", lower=lower, scan_trips=k,
            state_leaf_bytes=_leaf_bytes(state),
            data_bytes_global=data_global,
            data_bytes_per_device=data_per_dev,
            # DeviceStager._nbytes sums the converted host arrays — the
            # chunk plus the js schedule indices convert() appends
            model_h2d_bytes=data_global, pad_bytes=0, donated=True,
            data_arg_bytes=data_arg))
    return units


def _resident_unit(plan_: ExecutionPlan) -> List[_Unit]:
    spec, cfg = plan_.spec, plan_.cfg
    problem = spec.problem
    n, rows = plan_.features, plan_.rows
    sharded = plan_.shards > 1
    mesh = spec.mesh if sharded else None
    psum = sharded and plan_.reduction == PSUM
    lrows = plan_.shards * (-(-rows // plan_.shards)) if psum else rows
    epoch_fn = make_resident_epoch_fn(problem, cfg, plan_.scheme_name,
                                      spec.batch_size,
                                      rows=rows if psum else None)
    state = _state_avals(plan_)
    if sharded:
        state = _shard_tree(state, NamedSharding(mesh, PartitionSpec()))
        if psum:
            shardings = staging_shardings(
                mesh, (("batch", None), ("batch",)), [(lrows, n), (lrows,)])
        else:
            rep = NamedSharding(mesh, PartitionSpec())
            shardings = (rep, rep)
        X = _sds((lrows, n), jnp.float32, shardings[0])
        y = _sds((lrows,), jnp.float32, shardings[1])
    else:
        X = _sds((lrows, n), jnp.float32)
        y = _sds((lrows,), jnp.float32)
    key = _sds((2,), jnp.uint32)       # jax.random.PRNGKey layout

    def lower(epoch: int):
        del epoch   # the epoch enters via the key VALUE, not its shape
        # epoch_fn is partial(_run_one_epoch, problem, cfg, scheme, b,
        # rows=...) over the jit'd runner: lower the SAME jit object the
        # executor calls, statics included, so the audit shares its cache
        return epoch_fn.func.lower(*epoch_fn.args, state, X, y, key,
                                   **epoch_fn.keywords)

    data_global = _aval_bytes(X) + _aval_bytes(y)
    pad = data_global - rows * (n + 1) * 4
    return [_Unit(
        name=f"resident_epoch[rows={lrows}]", lower=lower,
        scan_trips=plan_.num_batches,
        state_leaf_bytes=_leaf_bytes(state),
        data_bytes_global=data_global,
        data_bytes_per_device=(_per_device_bytes(X, mesh)
                               + _per_device_bytes(y, mesh)),
        # record_h2d books the PRE-pad host corpus bytes (the README's
        # bytes_staged contract); the pad is a placement artifact
        model_h2d_bytes=rows * (n + 1) * 4, pad_bytes=pad,
        donated=False, key_bytes=_aval_bytes(key),
        data_arg_bytes=[_per_device_bytes(X, mesh),
                        _per_device_bytes(y, mesh)])]


def _build_units(plan_: ExecutionPlan) -> List[_Unit]:
    if plan_.placement == expmod.RESIDENT:
        return _resident_unit(plan_)
    return _streamed_units(plan_)


# ---------------------------------------------------------------------------
# lowered artifacts
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Analyzed:
    unit: _Unit
    stablehlo: str       # pre-optimization lowering (callbacks, dtypes)
    compiled_text: str   # optimized per-device HLO (collectives, aliasing)
    stablehlo_2: str     # second lowering, epoch-2 avals (cache rule)
    mem: Dict[str, float]


def _analyze_unit(unit: _Unit) -> _Analyzed:
    low1 = unit.lower(1)
    low2 = unit.lower(2)
    compiled = low1.compile()
    return _Analyzed(unit=unit, stablehlo=low1.as_text(),
                     compiled_text=compiled.as_text(),
                     stablehlo_2=low2.as_text(),
                     mem=memory_dict(compiled))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def _rule_collectives(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    model = HloCostModel(an.compiled_text, plan_.shards)
    counts = {k: v for k, v in model.cost().ici_counts.items() if v}
    inventory = json.dumps(counts) if counts else "none"
    mode = plan_.reduction if plan_.shards > 1 else "single-host"
    if mode in ("single-host", GATHER):
        # gather reshards at the staging put, OUTSIDE this program: any
        # collective here is a hidden transfer the contract forbids
        if counts:
            return RuleResult("collectives", FAIL,
                              f"{mode} plan lowered collectives: {inventory}")
        return RuleResult("collectives", PASS,
                          f"{mode}: zero collectives in the lowered module")
    if plan_.placement == expmod.STREAMED:
        # streamed psum: inputs stay batch-sharded through the scan, so the
        # partial-gradient all-reduce must be INSIDE it (>= one per batch
        # once loop trips multiply through) and nothing else may appear
        ar = counts.pop("all-reduce", 0)
        if counts:
            return RuleResult(
                "collectives", FAIL,
                f"psum plan lowered unexpected collective kinds: "
                f"{json.dumps(counts)} (all-reduce={ar:g})")
        if ar < an.unit.scan_trips:
            return RuleResult(
                "collectives", FAIL,
                f"psum plan lowered {ar:g} all-reduce(s); expected >= "
                f"{an.unit.scan_trips} (one partial-grad reduce per "
                f"scanned batch) — the reduction left the scan")
        return RuleResult(
            "collectives", PASS,
            f"psum: {ar:g} all-reduce over {an.unit.scan_trips} scanned "
            f"batches, no other collective kinds")
    # resident psum: GSPMD may keep the row-sharded corpus in place and
    # all-reduce partial gradients per batch, OR reshard via all-gather
    # (observed on small corpora: it hoists one gather of X,y out of the
    # batch loop and computes replicated) — both realize the reduction.
    # What it may NOT do is lower ZERO collectives (a psum program with no
    # cross-device traffic never combined the shards) or reach for kinds
    # outside the reduction family.
    family = {"all-reduce", "all-gather", "reduce-scatter"}
    alien = {k: v for k, v in counts.items() if k not in family}
    if alien:
        return RuleResult(
            "collectives", FAIL,
            f"psum plan lowered collective kinds outside the reduction "
            f"family: {json.dumps(alien)} (full inventory {inventory})")
    if not counts:
        return RuleResult(
            "collectives", FAIL,
            "psum plan lowered ZERO collectives: the shards were never "
            "combined — the reduction is silently wrong or the data was "
            "never sharded")
    return RuleResult(
        "collectives", PASS,
        f"psum: reduction realized as {inventory} (GSPMD picks all-reduce "
        f"of partials or input all-gather; both combine the shards)")


def _rule_donation(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    unit = an.unit
    if not unit.donated:
        return RuleResult(
            "donation", SKIP,
            "engine does not declare donation (resident epoch fn rebinds "
            "state; nothing to verify)")
    # the HloModule header records honored aliases:
    #   input_output_alias={ {0}: (0, {}, may-alias), ... }
    header = ""
    for line in an.compiled_text.splitlines():
        if "input_output_alias=" in line:
            header = line.split("input_output_alias=", 1)[1]
            break
    aliased = {int(m) for m in _ALIAS_ENTRY_RE.findall(header)}
    # state is argument 0: its flattened leaves are entry params 0..L-1;
    # zero-size slots (unused solver fields) legitimately stay un-aliased
    need = {i for i, nb in enumerate(unit.state_leaf_bytes) if nb > 0}
    missing = sorted(need - aliased)
    alias_sz = an.mem.get("alias_size_in_bytes")
    if missing:
        return RuleResult(
            "donation", FAIL,
            f"state params {missing} not aliased (donated but copied): "
            f"aliased={sorted(aliased)}, "
            f"state leaf bytes={unit.state_leaf_bytes}")
    ev = (f"params {sorted(need)} aliased in-place"
          + (f"; alias_size={alias_sz:.0f}B" if alias_sz is not None else ""))
    return RuleResult("donation", PASS, ev)


def _rule_dtypes(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    for label, text in (("compiled HLO", an.compiled_text),
                        ("stablehlo", an.stablehlo)):
        m = _F64_RE.search(text)
        if m:
            line = text[:m.start()].count("\n") + 1
            return RuleResult(
                "dtypes", FAIL,
                f"f64/c128 in {label} at line {line}: silent f32->f64 "
                f"promotion doubles every byte the access model counts")
    return RuleResult("dtypes", PASS, "module is free of f64/c128")


def _rule_callbacks(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    bad = []
    for text in (an.stablehlo, an.compiled_text):
        for m in _CALLBACK_TARGET_RE.finditer(text):
            target = m.group(1) or m.group(2) or ""
            if re.search(r"callback|xla_python|xla_ffi_python", target):
                bad.append(target)
    if bad:
        return RuleResult(
            "callbacks", FAIL,
            f"host callback(s) inside traced code: {sorted(set(bad))} — "
            f"a host round-trip per batch; route timing through obs spans")
    return RuleResult("callbacks", PASS, "no host-callback custom_calls")


def _rule_cache_keys(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    h1 = hashlib.sha256(an.stablehlo.encode()).hexdigest()[:12]
    h2 = hashlib.sha256(an.stablehlo_2.encode()).hexdigest()[:12]
    if an.stablehlo != an.stablehlo_2:
        return RuleResult(
            "cache_keys", FAIL,
            f"epoch-1 vs epoch-2 lowerings differ ({h1} != {h2}): every "
            f"epoch would recompile")
    return RuleResult("cache_keys", PASS,
                      f"epoch-1 and epoch-2 avals hit one lowering ({h1})")


def _rule_h2d(plan_: ExecutionPlan, an: _Analyzed) -> RuleResult:
    unit = an.unit
    model = HloCostModel(an.compiled_text, plan_.shards)
    entry_ops = model.comps.get(model.entry or "", [])
    entry_sizes = [_type_bytes(op.result_type) for op in entry_ops
                   if op.opcode == "parameter"]
    param_bytes = sum(entry_sizes)
    expect_sizes = (list(unit.state_leaf_bytes) + list(unit.data_arg_bytes)
                    + ([unit.key_bytes] if unit.key_bytes else []))
    expect = sum(expect_sizes)
    # XLA drops entry params the program never reads (a solver that ignores
    # its js schedule, say) — so match as multisets: every surviving entry
    # param must map onto a declared arg, and only whole args may vanish
    surplus = Counter(entry_sizes) - Counter(int(s) for s in expect_sizes)
    if surplus:
        return RuleResult(
            "h2d_bytes", FAIL,
            f"entry declares parameter bytes the model never staged: "
            f"{dict(surplus)} (entry {param_bytes}B vs model {expect}B = "
            f"state {unit.state_bytes} + data/device "
            f"{unit.data_bytes_per_device} + key {unit.key_bytes}) — the "
            f"lowered transfer surface drifted from AccessStats")
    dropped = expect - param_bytes
    if unit.data_arg_bytes and max(unit.data_arg_bytes) not in entry_sizes:
        return RuleResult(
            "h2d_bytes", FAIL,
            f"the data payload ({max(unit.data_arg_bytes)}B/device) was "
            f"eliminated from the entry computation — the lowered program "
            f"never reads the bytes AccessStats says it stages")
    # reconcile the staging model: the global staged payload must equal
    # what record_h2d books, up to the sharding zero-pad artifact
    if unit.data_bytes_global - unit.pad_bytes != unit.model_h2d_bytes:
        return RuleResult(
            "h2d_bytes", FAIL,
            f"global staged payload {unit.data_bytes_global}B - pad "
            f"{unit.pad_bytes}B != AccessStats model "
            f"{unit.model_h2d_bytes}B")
    per_dev = (unit.model_h2d_bytes // plan_.shards if plan_.shards > 1
               else unit.model_h2d_bytes)
    return RuleResult(
        "h2d_bytes", PASS,
        f"entry={param_bytes}B vs model {expect}B (state "
        f"{unit.state_bytes}B + data/device {unit.data_bytes_per_device}B"
        + (f" + key {unit.key_bytes}B" if unit.key_bytes else "") + ")"
        + (f"; {dropped}B of unused args eliminated at compile time"
           if dropped else "")
        + f"; AccessStats books {unit.model_h2d_bytes}B staged"
        + (f" (~{per_dev}B H2D/device)" if plan_.shards > 1 else "")
        + (f", pad {unit.pad_bytes}B" if unit.pad_bytes else ""))


_RULE_FNS = {
    "collectives": _rule_collectives,
    "donation": _rule_donation,
    "dtypes": _rule_dtypes,
    "callbacks": _rule_callbacks,
    "cache_keys": _rule_cache_keys,
    "h2d_bytes": _rule_h2d,
}


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def audit(spec_or_plan) -> AuditReport:
    """Statically verify a spec/plan's access contract — zero execution.

    Accepts an :class:`ExperimentSpec` (planned first) or an
    :class:`ExecutionPlan`; returns an :class:`AuditReport` with one
    pass/fail/skip :class:`RuleResult` per rule per lowered unit.
    """
    if isinstance(spec_or_plan, ExecutionPlan):
        plan_ = spec_or_plan
    elif isinstance(spec_or_plan, ExperimentSpec):
        plan_ = expmod.plan(spec_or_plan)
    else:
        raise TypeError(
            f"audit() wants an ExperimentSpec or ExecutionPlan, got "
            f"{type(spec_or_plan).__name__}")
    if plan_.shards > 1 and jax.device_count() < plan_.shards:
        raise AuditError(
            f"plan wants {plan_.shards} devices but only "
            f"{jax.device_count()} are visible — sharded plans lower "
            f"against their mesh (CI forces CPU devices via XLA_FLAGS)")
    units = _build_units(plan_)
    audits = []
    for unit in units:
        an = _analyze_unit(unit)
        audits.append(UnitAudit(
            unit=unit.name,
            results=[_RULE_FNS[r](plan_, an) for r in RULES]))
    return AuditReport(backend=plan_.backend, reduction=plan_.reduction,
                       shards=plan_.shards, units=audits)


def check(plan_: ExecutionPlan) -> AuditReport:
    """``plan(..., audit=True)`` helper: audit and raise on any failure."""
    report = audit(plan_)
    if not report.ok:
        lines = [f"  {unit}: [{r.rule}] {r.evidence}"
                 for unit, r in report.failures()]
        raise AuditError(
            "static audit failed for backend "
            f"{plan_.backend!r}:\n" + "\n".join(lines))
    return report
