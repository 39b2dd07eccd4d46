"""Tier-2b: compile the corpus and audit the program the device will run.

The tier-1 rules and the sharding flow both judge the TRACED program; XLA's
partitioner then rewrites it — inserting all-gathers, fusing buffers,
deciding what donation actually aliases. This module lowers every corpus
entry point with its site's real shardings and donation
(``jit(fn, **contract).lower(*args).compile()`` on the forced 8-device CPU
mesh — the partitioned HLO is identical to TPU modulo backend fusion),
then parses the optimized HLO text for the actual collectives
(all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
with replica group size, element type, byte size) and reads
``memory_analysis()`` for the executable's peak.

Per-site results reconcile two ways:

- against the sharding flow's prediction (plus the tier-1 wire estimate
  for manual shard_map collectives): an actual collective family the
  static tiers never predicted is an *unexplained* collective, reported
  per site (advisory — fusion heuristics move small collectives around);
- against the committed ``tools/hlo_baseline.json``: exact collective
  counts by op x dtype, wire bytes within tolerance, HBM peak within 5%.
  Any diff fails ``tools/lint_programs.py --hlo`` naming the op, the
  dtype, and the site — this is the CI gate the Pallas-kernel and
  hybrid-mesh PRs land behind.

Nothing here executes a program: ``.compile()`` builds the executable but
never runs it, so the audit stays safe on any host (and stays inside the
60s CPU lint budget — ~15s for the 7-program corpus).
"""

from __future__ import annotations

import json
import os
import re
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..observability import metrics as _metrics
from .analyzer import ProgramSpec, collect_wire
from .findings import Finding
from .sharding_flow import flow_findings

__all__ = ["SiteAudit", "HloDiff", "audit_spec", "audit_corpus",
           "parse_hlo_collectives", "default_hlo_baseline_path",
           "load_hlo_baseline", "save_hlo_baseline", "audits_to_baseline",
           "diff_against_baseline", "inject_replicated_arg",
           "WIRE_TOLERANCE", "HBM_TOLERANCE"]

#: relative tolerances the baseline diff allows before failing the gate
WIRE_TOLERANCE = 0.10
HBM_TOLERANCE = 0.05

#: HLO instruction names we count (async *-start variants fold into the
#: base op; *-done carries no payload of its own)
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")

#: tier-1 wire-estimate primitive -> HLO collective family
_PRIM_FAMILY = {
    "psum": "all-reduce", "pmax": "all-reduce", "pmin": "all-reduce",
    "all_gather": "all-gather", "reduce_scatter": "reduce-scatter",
    "all_to_all": "all-to-all", "ppermute": "collective-permute",
    "pbroadcast": "collective-permute",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# one HLO instruction: `%name = f32[8,16]{1,0} all-reduce(...), ...` — the
# result type may also be a TUPLE (`= (f32[16,4]{1,0}, f32[16,4]{1,0})
# all-to-all(...)`, XLA's tuple-form all-to-all), so capture everything
# between `=` and the op name lazily and pull the element types out of it
_INSTR_RE = re.compile(
    r"=\s*(\(?\s*[a-z0-9]+\[[0-9,]*\][^=]*?)\s+"
    r"(" + "|".join(_COLLECTIVE_OPS) + r")(-start)?\(")

# one shaped element type inside the (possibly tuple) result type
_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_GROUPS_RE = re.compile(
    r"replica_groups=(?:\[(\d+),(\d+)\]<=\[\d+\]|\{(\{[^}]*\}[^}]*)\})")


@dataclass(frozen=True)
class HloCollective:
    """One collective instruction in the partitioned program."""

    op: str                   # all-reduce | all-gather | ...
    dtype: str                # HLO element type (f32, bf16, s32, ...)
    shape: Tuple[int, ...]    # per-device output shape
    group_size: int           # devices per replica group
    out_bytes: int            # per-device output payload

    @property
    def key(self) -> str:
        return f"{self.op}|{self.dtype}"

    @property
    def wire_bytes(self) -> int:
        """Per-device receive-side bytes (the repo's plan convention)."""
        n, b = max(self.group_size, 1), self.out_bytes
        if n <= 1:
            return 0
        if self.op == "all-reduce":
            return 2 * (n - 1) * b // n
        if self.op == "all-gather":          # out is the gathered buffer
            return (n - 1) * b // n
        if self.op == "reduce-scatter":      # out is the scattered shard
            return (n - 1) * b
        if self.op == "all-to-all":
            return (n - 1) * b // n
        return b                             # collective-permute


def parse_hlo_collectives(text: str,
                          device_count: Optional[int] = None
                          ) -> List[HloCollective]:
    """Extract every collective instruction from optimized HLO text."""
    ndev = device_count or jax.device_count()
    out: List[HloCollective] = []
    for line in text.splitlines():
        # wide tuples carry `/*index=5*/` comments whose `=` breaks the
        # result-type match — drop comments before parsing
        line = re.sub(r"/\*.*?\*/", "", line)
        m = _INSTR_RE.search(line)
        if not m:
            continue
        types, op = _TYPE_RE.findall(m.group(1)), m.group(2)
        types = [(dt, dims) for dt, dims in types if dt != "token"]
        if not types:
            continue
        # tuple results (one element per peer) sum into one instruction;
        # dtype/shape report the first element
        dtype, dims = types[0]
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        nbytes = 0
        for dt, dm in types:
            sh = tuple(int(d) for d in dm.split(",")) if dm else ()
            elems = int(np.prod(sh, dtype=np.int64)) if sh else 1
            nbytes += elems * _DTYPE_BYTES.get(dt, 4)
        gm = _GROUPS_RE.search(line)
        if gm and gm.group(2) is not None:       # iota [ngroups,gsize]<=[N]
            gsize = int(gm.group(2))
        elif gm and gm.group(3) is not None:     # explicit {{0,1},{2,3}}
            first = gm.group(3).split("}")[0].lstrip("{")
            gsize = len([t for t in first.split(",") if t.strip() != ""])
        else:
            gsize = ndev
        out.append(HloCollective(op=op, dtype=dtype, shape=shape,
                                 group_size=gsize, out_bytes=nbytes))
    return out


@dataclass
class SiteAudit:
    """The audited truth for one corpus entry point."""

    site: str
    collectives: List[HloCollective] = field(default_factory=list)
    counts: Dict[str, int] = field(default_factory=dict)   # "op|dtype" -> n
    wire_bytes: int = 0
    hbm: Dict[str, int] = field(default_factory=dict)
    cost: Dict[str, float] = field(default_factory=dict)   # cost_analysis
    compile_seconds: float = 0.0
    predicted: Dict[str, int] = field(default_factory=dict)  # family->bytes
    unexplained: List[str] = field(default_factory=list)     # families
    error: Optional[str] = None

    def as_dict(self) -> Dict[str, Any]:
        return {
            "site": self.site,
            "counts": dict(sorted(self.counts.items())),
            "wire_bytes": self.wire_bytes,
            "hbm_peak_bytes": self.hbm.get("peak", 0),
            "flops": self.cost.get("flops", 0.0),
            "bytes_accessed": self.cost.get("bytes_accessed", 0.0),
            "compile_seconds": round(self.compile_seconds, 3),
            "predicted": dict(sorted(self.predicted.items())),
            "unexplained": list(self.unexplained),
            "error": self.error,
        }


def _memory_analysis(compiled) -> Dict[str, int]:
    """Executable memory accounting; peak follows observability/memory.py:
    temp + argument + output + generated code - aliased."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    fields = {
        "temp": "temp_size_in_bytes",
        "argument": "argument_size_in_bytes",
        "output": "output_size_in_bytes",
        "code": "generated_code_size_in_bytes",
        "alias": "alias_size_in_bytes",
    }
    out: Dict[str, int] = {}
    for k, attr in fields.items():
        v = getattr(ma, attr, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["peak"] = (out.get("temp", 0) + out.get("argument", 0)
                       + out.get("output", 0) + out.get("code", 0)
                       - out.get("alias", 0))
    return out


def _cost_analysis(compiled) -> Dict[str, float]:
    """Executable cost properties — the roofline attribution feed
    (observability/attribution.py): per-device FLOPs and HBM bytes
    accessed per execution. Empty when the backend declines."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return {}
    out: Dict[str, float] = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed")):
        try:
            v = float(ca.get(key, 0.0))
        except (TypeError, ValueError, AttributeError):
            continue
        if v:
            out[name] = v
    return out


#: payloads below this never count as "unexplained" — fusion freely creates
#: and moves small bookkeeping collectives (loop counters, rng keys)
_UNEXPLAINED_MIN_BYTES = 256 * 1024

#: the SPMD partitioner may lower a predicted collective as a ring of a
#: different family (windowed einsum turns a matmul all-reduce into a
#: collective-permute chain; an all-reduce splits into reduce-scatter +
#: all-gather). An emitted family with no direct prediction is still
#: explained when any of its possible source families was predicted.
_DECOMPOSED_FAMILIES = {
    "collective-permute": ("all-reduce", "all-gather", "reduce-scatter"),
    "reduce-scatter": ("all-reduce",),
}


def audit_spec(spec: ProgramSpec) -> SiteAudit:
    """Lower-and-compile one corpus entry with its contract's shardings,
    parse the partitioned HLO, and reconcile against the static tiers."""
    audit = SiteAudit(site=spec.name)
    t0 = time.perf_counter()
    jit_kwargs: Dict[str, Any] = {}
    if spec.sharding is not None:
        jit_kwargs.update(spec.sharding.jit_kwargs())
    if spec.contract.donate_argnums:
        jit_kwargs["donate_argnums"] = tuple(spec.contract.donate_argnums)
    try:
        with warnings.catch_warnings():
            # CPU declines donation aliasing with a warning; not the
            # audit's concern (tier-1 owns donation hygiene)
            warnings.simplefilter("ignore")
            with spec.trace_context():
                compiled = (jax.jit(spec.fn, **jit_kwargs)
                            .lower(*spec.args).compile())
            text = compiled.as_text()
    except Exception as e:  # noqa: BLE001 - surfaced on the audit record
        audit.error = f"{type(e).__name__}: {e}"
        audit.compile_seconds = time.perf_counter() - t0
        return audit
    audit.compile_seconds = time.perf_counter() - t0
    audit.collectives = parse_hlo_collectives(text)
    for c in audit.collectives:
        audit.counts[c.key] = audit.counts.get(c.key, 0) + 1
        audit.wire_bytes += c.wire_bytes
    audit.hbm = _memory_analysis(compiled)
    audit.cost = _cost_analysis(compiled)

    # static prediction: sharding-flow events + tier-1 manual-region wire
    predicted: Dict[str, int] = {}
    try:
        with spec.trace_context():
            closed = jax.make_jaxpr(spec.fn)(*spec.args)
        for prim, b in collect_wire(closed).items():
            fam = _PRIM_FAMILY.get(prim)
            if fam:
                predicted[fam] = predicted.get(fam, 0) + b
        if spec.sharding is not None:
            result, _ = flow_findings(spec.name, closed, spec.sharding,
                                      spec.args)
            for kind, b in result.predicted_kinds().items():
                fam = {"all-reduce": "all-reduce",
                       "all-gather": "all-gather",
                       "replicate": "all-gather",
                       "reshard": "all-to-all"}.get(kind)
                if fam:
                    predicted[fam] = predicted.get(fam, 0) + b
    except Exception:
        pass  # prediction is advisory; the baseline diff is the gate
    audit.predicted = predicted
    by_family: Dict[str, int] = {}
    for c in audit.collectives:
        by_family[c.op] = by_family.get(c.op, 0) + c.wire_bytes
    audit.unexplained = sorted(
        fam for fam, b in by_family.items()
        if b >= _UNEXPLAINED_MIN_BYTES and predicted.get(fam, 0) == 0
        and not any(predicted.get(src, 0)
                    for src in _DECOMPOSED_FAMILIES.get(fam, ())))

    if _metrics.enabled():
        _metrics.histogram("analysis.hlo.audit_seconds",
                           audit.compile_seconds, site=spec.name)
        for key, n in audit.counts.items():
            op, dtype = key.split("|", 1)
            _metrics.counter("analysis.hlo.collectives", n, op=op,
                             dtype=dtype)
        if audit.hbm.get("peak"):
            _metrics.gauge("analysis.hlo.hbm_peak_bytes",
                           audit.hbm["peak"], site=spec.name)
    return audit


def audit_corpus(specs: Sequence[ProgramSpec]) -> List[SiteAudit]:
    return [audit_spec(s) for s in specs]


# ---------------------------------------------------------------- baseline

def default_hlo_baseline_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "tools", "hlo_baseline.json")


def load_hlo_baseline(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or default_hlo_baseline_path()
    if not os.path.exists(path):
        return {"version": 1, "device_count": jax.device_count(),
                "sites": {}, "history": []}
    with open(path) as f:
        return json.load(f)


def save_hlo_baseline(baseline: Dict[str, Any],
                      path: Optional[str] = None):
    path = path or default_hlo_baseline_path()
    with open(path, "w") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
        f.write("\n")


def audits_to_baseline(audits: Sequence[SiteAudit],
                       reason: str = "",
                       baseline: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Fold the audited truth into a (new or existing) baseline dict."""
    base = baseline or {"version": 1, "device_count": jax.device_count(),
                        "sites": {}, "history": []}
    base["device_count"] = jax.device_count()
    base["sites"] = {
        a.site: {
            "collectives": dict(sorted(a.counts.items())),
            "wire_bytes": int(a.wire_bytes),
            "hbm_peak_bytes": int(a.hbm.get("peak", 0)),
        }
        for a in audits if a.error is None
    }
    base.setdefault("history", []).append({
        "date": time.strftime("%Y-%m-%d"),
        "reason": reason or "(none given)",
        "sites": sorted(base["sites"]),
    })
    return base


@dataclass(frozen=True)
class HloDiff:
    """One divergence between the audited program and the baseline."""

    site: str
    kind: str        # collective-count | wire-bytes | hbm-peak | site-*
    op: str = ""
    dtype: str = ""
    baseline: int = 0
    actual: int = 0
    detail: str = ""

    def render(self) -> str:
        what = f"{self.op} {self.dtype}".strip() or self.kind
        return (f"[{self.site}] {self.kind}: {what} "
                f"baseline={self.baseline} actual={self.actual}"
                + (f" — {self.detail}" if self.detail else ""))


def _rel_exceeds(baseline: int, actual: int, tol: float) -> bool:
    if baseline == actual:
        return False
    scale = max(abs(baseline), 1)
    return abs(actual - baseline) / scale > tol


def diff_against_baseline(audits: Sequence[SiteAudit],
                          baseline: Dict[str, Any],
                          wire_tol: float = WIRE_TOLERANCE,
                          hbm_tol: float = HBM_TOLERANCE
                          ) -> List[HloDiff]:
    """The CI gate: every way the partitioned corpus drifted from the
    committed truth, each naming the op, dtype, and site."""
    diffs: List[HloDiff] = []
    sites = baseline.get("sites", {})
    audited = {a.site: a for a in audits}
    ndev = baseline.get("device_count")
    if ndev is not None and ndev != jax.device_count():
        diffs.append(HloDiff(
            site="(env)", kind="device-count", baseline=int(ndev),
            actual=jax.device_count(),
            detail="baseline was recorded on a different mesh; "
                   "re-record with --update-hlo-baseline"))
        return diffs
    for name, a in audited.items():
        if a.error is not None:
            diffs.append(HloDiff(site=name, kind="compile-error",
                                 detail=a.error))
            continue
        b = sites.get(name)
        if b is None:
            diffs.append(HloDiff(
                site=name, kind="site-new",
                detail="site not in hlo_baseline.json; run "
                       "--update-hlo-baseline --reason '...'"))
            continue
        bc = dict(b.get("collectives", {}))
        for key in sorted(set(bc) | set(a.counts)):
            nb, na = int(bc.get(key, 0)), int(a.counts.get(key, 0))
            if nb != na:
                op, dtype = key.split("|", 1)
                diffs.append(HloDiff(
                    site=name, kind="collective-count", op=op,
                    dtype=dtype, baseline=nb, actual=na,
                    detail=f"{'extra' if na > nb else 'missing'} "
                           f"{abs(na - nb)} {op}({dtype}) in the "
                           "partitioned program"))
        bw = int(b.get("wire_bytes", 0))
        if _rel_exceeds(bw, a.wire_bytes, wire_tol):
            diffs.append(HloDiff(
                site=name, kind="wire-bytes", baseline=bw,
                actual=a.wire_bytes,
                detail=f"per-device wire bytes moved more than "
                       f"{wire_tol:.0%}"))
        bh = int(b.get("hbm_peak_bytes", 0))
        ah = int(a.hbm.get("peak", 0))
        if _rel_exceeds(bh, ah, hbm_tol):
            diffs.append(HloDiff(
                site=name, kind="hbm-peak", baseline=bh, actual=ah,
                detail=f"executable memory peak moved more than "
                       f"{hbm_tol:.0%}"))
    for name in sorted(set(sites) - set(audited)):
        diffs.append(HloDiff(
            site=name, kind="site-missing",
            detail="site in hlo_baseline.json but not in this corpus; "
                   "run --update-hlo-baseline --reason '...'"))
    if _metrics.enabled() and diffs:
        _metrics.counter("analysis.hlo.baseline_diffs", len(diffs))
    return diffs


def unexplained_findings(audits: Sequence[SiteAudit]) -> List[Finding]:
    """Advisory (info) findings for actual collective families the static
    tiers never predicted — never gates, but shows up in reports."""
    out: List[Finding] = []
    for a in audits:
        for fam in a.unexplained:
            out.append(Finding(
                rule="spmd-predict-divergence", site=a.site,
                severity="info",
                message=(f"partitioned program contains {fam} traffic the "
                         "sharding flow and tier-1 wire model never "
                         "predicted — check the site's ShardingContract"),
                data=(fam,)))
    return out


# --------------------------------------------------------------- injection

def inject_replicated_arg(spec: ProgramSpec,
                          argnum: Optional[int] = None) -> ProgramSpec:
    """Gate demo: wrap a corpus entry so one sharded argument is forced
    fully replicated via with_sharding_constraint — the broken sharding
    annotation of the acceptance criteria. GSPMD must insert the
    all-gather, and the baseline diff names it."""
    from dataclasses import replace as _replace

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .sharding_flow import flat_arg_specs
    if spec.sharding is None or spec.sharding.mesh is None:
        raise ValueError(f"site {spec.name!r} declares no compilable "
                         "ShardingContract to break")
    if argnum is None:
        flat = flat_arg_specs(spec.args, spec.sharding.in_shardings)
        pos = 0
        argnum = -1
        for ai, arg in enumerate(spec.args):
            nleaves = len(jax.tree_util.tree_leaves(arg))
            if any(s is not None and any(s)
                   for s in flat[pos:pos + nleaves]):
                argnum = ai
                break
            pos += nleaves
        if argnum < 0:
            raise ValueError(f"site {spec.name!r} has no sharded argument "
                             "to replicate")
    repl = NamedSharding(spec.sharding.mesh, P())
    fn, idx = spec.fn, int(argnum)

    def broken(*args):
        args = list(args)
        args[idx] = jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(a, repl), args[idx])
        return fn(*args)

    return _replace(spec, fn=broken)
