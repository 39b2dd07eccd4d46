"""The jaxpr walker: trace an entry point, run every rule over it.

``analyze_fn(name, fn, args, contract)`` traces fn to a closed jaxpr
(jax.make_jaxpr — abstract evaluation only, no device execution, so the
whole corpus lints on a CPU-only host) and walks it:

- the walk recurses through EVERY sub-jaxpr a primitive carries (pjit,
  scan, while, cond branches, custom_vjp, ...), so rules see the fully
  inlined program shape;
- crossing a ``shard_map`` opens a Region: the mesh's axis sizes plus
  which axes are manual (mesh axes minus the params' ``auto`` set) — the
  context the collective rules judge against;
- collectives accumulate per-device receive-side wire-byte estimates into
  the context, reconciled at the end against the site's own plan
  accounting (SiteContract.expected_wire_bytes).

Findings flow back as a Report and, when observability is on, through the
metrics registry (``analysis.*`` — see observability/README.md).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

try:  # jax >= 0.4.35 moves the IR types to jax.extend.core
    from jax.extend.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover - older jax
    from jax.core import ClosedJaxpr, Jaxpr

from ..observability import metrics as _metrics
from .findings import Finding, Report, drain_ambient
from .rules import (COLLECTIVES, Rule, collective_axes, default_rules,
                    wire_bytes)
from .sharding_flow import ShardingContract, flow_findings

__all__ = ["SiteContract", "ProgramSpec", "Region", "Context",
           "analyze_fn", "analyze_closed", "analyze_corpus",
           "collect_wire"]


@dataclass(frozen=True)
class SiteContract:
    """What an entry point promises — which rules apply and how hard.

    ``one_compile``: the site claims a fixed number of compilations
    (serving decode, the train step), so signature-level recompile hazards
    are findings. ``donate_argnums``: the donation the real call site
    passes to jit (None = no donation contract declared; donation rules
    skip). ``expected_wire_bytes``: the site's own static accounting of
    bytes-on-wire per execution (comm_opt/resharding plans), reconciled
    against the analyzer's estimate within ``wire_tolerance``x.
    """

    one_compile: bool = False
    donate_argnums: Optional[Tuple[int, ...]] = None
    donation_threshold: int = 64 * 1024
    wire_threshold: int = 1 << 20
    expected_wire_bytes: Optional[int] = None
    wire_tolerance: float = 2.0


@dataclass(frozen=True)
class ProgramSpec:
    """One corpus entry: a traceable entry point plus its contract.

    ``sharding`` (tier 2) declares the shardings the site's jit is built
    with: the flow rules judge against it and hlo_audit compiles with it —
    without it the partitioner sees unconstrained args and elides the very
    collectives the audit exists to count.

    ``mesh`` is the mesh the product enters (``jax.set_mesh``) around its
    own trace of the program, when what the trace builds depends on it;
    every tracer of the spec enters it the same way (``trace_context``) —
    around the trace, since jax rejects ``set_mesh`` inside one."""

    name: str
    fn: Callable
    args: Tuple
    contract: SiteContract = SiteContract()
    argnames: Optional[Tuple[str, ...]] = None
    sharding: Optional[ShardingContract] = None
    mesh: Optional[Any] = None

    def trace_context(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())


@dataclass(frozen=True)
class Region:
    """One shard_map scope: the mesh visible inside it."""

    mesh_axes: Dict[str, int]  # full axis -> size
    manual: frozenset          # axes named manual in this region
    path: str


@dataclass
class Context:
    """Mutable walk state handed to every rule hook."""

    site: str
    contract: SiteContract
    donated: Optional[Tuple[bool, ...]] = None   # aligned to top invars
    arg_names: Optional[Tuple[str, ...]] = None  # aligned to top invars
    region: Optional[Region] = None              # innermost shard_map
    path: str = ""                               # current eqn path
    wire: Dict[str, int] = field(default_factory=dict)  # prim -> bytes

    def arg_name(self, i: int) -> str:
        if self.arg_names is not None and i < len(self.arg_names):
            return self.arg_names[i]
        return f"arg[{i}]"


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    shape = getattr(mesh, "shape", None)
    if shape:
        return {str(k): int(v) for k, v in dict(shape).items()}
    return {str(a): int(s) for a, s in
            zip(mesh.axis_names, mesh.devices.shape)}


def _sub_jaxprs(eqn):
    """(label, jaxpr-or-closed) for every sub-program an eqn carries,
    EXCEPT shard_map (which the walker special-cases to open a Region)."""
    for k, v in eqn.params.items():
        seq = v if isinstance(v, (tuple, list)) else (v,)
        for j, sub in enumerate(seq):
            if isinstance(sub, (Jaxpr, ClosedJaxpr)):
                label = k if len(seq) == 1 else f"{k}[{j}]"
                yield label, sub


def _as_open(jaxpr):
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def _walk(jaxpr, ctx: Context, rules: Sequence[Rule], report: Report,
          region: Optional[Region], path: str):
    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        epath = f"{path}/{i}:{prim}"
        ctx.region, ctx.path = region, epath
        for rule in rules:
            report.extend(rule.check_eqn(eqn, ctx))
        if prim in COLLECTIVES and region is not None:
            n = 1
            for a in collective_axes(eqn):
                n *= region.mesh_axes.get(a, 1)
            b = wire_bytes(eqn, n)
            if b:
                ctx.wire[prim] = ctx.wire.get(prim, 0) + b
        if prim == "shard_map":
            mesh = eqn.params.get("mesh")
            auto = frozenset(eqn.params.get("auto", frozenset()))
            sizes = _mesh_axis_sizes(mesh) if mesh is not None else {}
            inner = Region(mesh_axes=sizes,
                           manual=frozenset(sizes) - auto,
                           path=epath)
            _walk(_as_open(eqn.params["jaxpr"]), ctx, rules, report,
                  inner, epath)
            continue
        for label, sub in _sub_jaxprs(eqn):
            _walk(_as_open(sub), ctx, rules, report, region,
                  f"{epath}.{label}")


def _flat_donation(args: Tuple, donate_argnums: Optional[Tuple[int, ...]],
                   argnames: Optional[Tuple[str, ...]]):
    """(donated mask, names) aligned with make_jaxpr's flattened invars."""
    donated: List[bool] = []
    names: List[str] = []
    dset = set(donate_argnums or ())
    for ai, arg in enumerate(args):
        base = (argnames[ai] if argnames and ai < len(argnames)
                else f"arg{ai}")
        paths, _ = jax.tree_util.tree_flatten_with_path(arg)
        for keypath, _ in paths:
            donated.append(ai in dset)
            names.append(base + jax.tree_util.keystr(keypath))
    mask = tuple(donated) if donate_argnums is not None else None
    return mask, tuple(names)


def analyze_closed(name: str, closed: ClosedJaxpr, contract: SiteContract,
                   donated: Optional[Tuple[bool, ...]] = None,
                   arg_names: Optional[Tuple[str, ...]] = None,
                   rules: Optional[Sequence[Rule]] = None) -> Report:
    """Run every rule over one already-traced closed jaxpr."""
    rules = list(rules) if rules is not None else default_rules()
    report = Report(programs=[name])
    ctx = Context(site=name, contract=contract, donated=donated,
                  arg_names=arg_names)
    t0 = time.perf_counter()
    ctx.path = "(signature)"
    for rule in rules:
        report.extend(rule.check_program(closed, ctx))
    _walk(closed.jaxpr, ctx, rules, report, region=None, path=name)
    ctx.region, ctx.path = None, "(summary)"
    for rule in rules:
        report.extend(rule.check_summary(ctx))
    seconds = time.perf_counter() - t0
    if _metrics.enabled():
        _metrics.counter("analysis.programs", 1)
        _metrics.histogram("analysis.seconds", seconds, site=name)
        for f in report.findings:
            _metrics.counter("analysis.findings", 1, rule=f.rule,
                             severity=f.severity)
        for op, b in ctx.wire.items():
            _metrics.counter("analysis.collective.bytes", b, op=op)
    return report


def analyze_fn(name: str, fn: Callable, args: Tuple,
               contract: SiteContract = SiteContract(),
               argnames: Optional[Tuple[str, ...]] = None,
               rules: Optional[Sequence[Rule]] = None,
               sharding: Optional[ShardingContract] = None,
               trace_context=contextlib.nullcontext) -> Report:
    """Trace fn(*args) abstractly and lint the resulting program. With a
    ShardingContract declared, the tier-2 sharding flow runs over the same
    trace (spmd-* rules)."""
    with trace_context():
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    donated, names = _flat_donation(args, contract.donate_argnums, argnames)
    report = analyze_closed(name, closed, contract, donated=donated,
                            arg_names=names, rules=rules)
    if sharding is not None:
        _, findings = flow_findings(name, closed, sharding, args,
                                    out_shape=out_shape)
        report.extend(findings)
        if _metrics.enabled():
            for f in findings:
                _metrics.counter("analysis.findings", 1, rule=f.rule,
                                 severity=f.severity)
    return report


def analyze_spec(spec: ProgramSpec,
                 rules: Optional[Sequence[Rule]] = None) -> Report:
    return analyze_fn(spec.name, spec.fn, spec.args, spec.contract,
                      argnames=spec.argnames, rules=rules,
                      sharding=spec.sharding,
                      trace_context=spec.trace_context)


def collect_wire(closed: ClosedJaxpr) -> Dict[str, int]:
    """Per-primitive receive-side wire-byte estimate for the collectives
    inside the program's manual shard_map regions — the tier-1 model,
    exposed for hlo_audit's prediction reconcile."""
    wire: Dict[str, int] = {}

    def walk(jaxpr, region: Optional[Region]):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in COLLECTIVES and region is not None:
                n = 1
                for a in collective_axes(eqn):
                    n *= region.mesh_axes.get(a, 1)
                b = wire_bytes(eqn, n)
                if b:
                    wire[prim] = wire.get(prim, 0) + b
            if prim == "shard_map":
                mesh = eqn.params.get("mesh")
                auto = frozenset(eqn.params.get("auto", frozenset()))
                sizes = _mesh_axis_sizes(mesh) if mesh is not None else {}
                walk(_as_open(eqn.params["jaxpr"]),
                     Region(mesh_axes=sizes,
                            manual=frozenset(sizes) - auto, path=""))
                continue
            for _, sub in _sub_jaxprs(eqn):
                walk(_as_open(sub), region)

    walk(closed.jaxpr, None)
    return wire


def analyze_corpus(specs: Sequence[ProgramSpec],
                   rules: Optional[Sequence[Rule]] = None
                   ) -> Tuple[Report, List[Tuple[str, str]]]:
    """Lint every spec; returns (merged deduped report, [(name, error)]
    for specs whose TRACE failed — a trace failure is surfaced as a
    finding too (rule ``trace-error``), since a corpus entry silently
    dropping out would un-gate its rules). Ambient findings recorded
    during corpus construction (``findings.record_ambient``, e.g.
    comm-quant-downgrade) are folded in."""
    merged = Report()
    merged.extend(drain_ambient())
    errors: List[Tuple[str, str]] = []
    for spec in specs:
        try:
            rep = analyze_spec(spec, rules=rules)
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            msg = f"{type(e).__name__}: {e}"
            errors.append((spec.name, msg))
            merged.add(Finding(
                rule="trace-error", site=spec.name, severity="error",
                message=f"entry point failed to trace: {msg[:300]}",
                data=(type(e).__name__,)))
            merged.programs.append(spec.name)
            continue
        merged.merge(rep)
    return merged.dedup(), errors
