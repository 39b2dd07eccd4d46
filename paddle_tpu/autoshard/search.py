"""Prune, rank, and emit the winning layout as a ShardingContract.

``search_train_step(model, optimizer, mesh=...)`` is the whole loop:

1. build (or borrow) a probe ``ShardedTrainStep`` under the hand-written
   seed layout and trace its step jaxpr ONCE — the jaxpr is
   layout-independent, so every candidate is scored against the same
   trace with nothing compiled;
2. enumerate the deduped candidate space (``space.enumerate_candidates``)
   plus the seed layout itself, always candidate 0;
3. score each candidate (``cost.score_candidate``) and reject
   HBM-infeasible or batch-indivisible layouts outright;
4. rank by predicted step floor (max per-resource roofline), wire bytes
   and HBM pressure as deterministic tie-breaks, the seed winning all
   remaining ties — the searched layout is never predicted-worse than
   the seed by construction.

The winner converts to jax types on demand: ``winner_mesh`` /
``winner_param_specs`` feed straight into
``make_sharded_train_step(..., autoshard=True)`` and
``SearchResult.winner_contract()`` yields the
``analysis.ShardingContract`` the validate stage and the CI gate
re-audit.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from ..observability import attribution
from ..observability import metrics as _metrics
from . import cost as _cost
from . import space as _space

__all__ = [
    "RankedCandidate", "SearchResult", "search_train_step",
    "seed_candidate", "to_partition_spec", "winner_mesh",
    "winner_param_specs",
]


def to_partition_spec(spec: Optional[Tuple[Tuple[str, ...], ...]]):
    """Canonical tuple spec -> jax PartitionSpec."""
    from jax.sharding import PartitionSpec as P

    if not spec:
        return P()
    entries = []
    for e in spec:
        if not e:
            entries.append(None)
        elif len(e) == 1:
            entries.append(e[0])
        else:
            entries.append(tuple(e))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


@dataclass
class RankedCandidate:
    candidate: _space.Candidate
    cost: _cost.CandidateCost
    rank: int = 0
    is_seed: bool = False

    def row(self) -> Dict[str, Any]:
        """One ranked-table row: everything the CLI/bench print."""
        return {
            "rank": self.rank,
            "layout": self.candidate.name,
            "family": self.candidate.family,
            "mesh": {a: n for a, n in self.candidate.mesh_axes if n > 1},
            "seed": self.is_seed,
            "floor_ms": round(self.cost.floor_ms, 6),
            "floors_ms": {k: round(v, 6)
                          for k, v in self.cost.floors_ms.items()},
            "binding": self.cost.binding,
            "wire_bytes_per_device": round(
                self.cost.wire_bytes_per_device, 1),
            "hbm_fit_bytes": int(self.cost.hbm_fit_bytes),
            "fits": self.cost.fits,
            "compute_split": self.cost.compute_split,
            "n_events": self.cost.n_events,
            "predicted_families": dict(sorted(
                self.cost.predicted_families.items())),
        }


@dataclass
class SearchResult:
    ranked: List[RankedCandidate] = field(default_factory=list)
    rejected: List[Tuple[str, str]] = field(default_factory=list)
    hw_name: str = ""
    device_count: int = 0
    batch_shape: Tuple[int, ...] = ()
    search_seconds: float = 0.0
    flat_totals: Dict[str, float] = field(default_factory=dict)

    @property
    def winner(self) -> Optional[RankedCandidate]:
        return self.ranked[0] if self.ranked else None

    @property
    def seed(self) -> Optional[RankedCandidate]:
        for rc in self.ranked:
            if rc.is_seed:
                return rc
        return None

    def table(self) -> List[Dict[str, Any]]:
        return [rc.row() for rc in self.ranked]

    def as_dict(self) -> Dict[str, Any]:
        return {
            "hw": self.hw_name,
            "device_count": self.device_count,
            "batch_shape": list(self.batch_shape),
            "search_seconds": round(self.search_seconds, 3),
            "candidates": len(self.ranked),
            "rejected": [{"layout": n, "reason": r}
                         for n, r in self.rejected],
            "winner": (self.winner.row() if self.winner else None),
            "table": self.table(),
        }

    def winner_contract(self, probe) -> Any:
        """The winner as an ``analysis.ShardingContract`` — built by
        re-deriving the step's in/out shardings under the winning layout
        (what ``ShardedTrainStep`` would jit with)."""
        win = self.winner
        if win is None or win.is_seed:
            return probe.sharding_contract()
        import numpy as _np

        from ..distributed.fleet.utils import make_sharded_train_step

        st = make_sharded_train_step(
            probe.model, probe.optimizer,
            mesh=winner_mesh(win.candidate),
            param_specs=winner_param_specs(win.candidate))
        return st.sharding_contract()


def winner_mesh(candidate: _space.Candidate, devices=None):
    """The candidate's mesh over the physical devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = tuple(a for a, _n in candidate.mesh_axes)
    shape = tuple(n for _a, n in candidate.mesh_axes)
    world = 1
    for n in shape:
        world *= n
    return Mesh(np.asarray(devices[:world]).reshape(shape), names)


def winner_param_specs(candidate: _space.Candidate) -> Dict[str, Any]:
    """{param name: PartitionSpec} for ``ShardedTrainStep(param_specs=)``."""
    return {name: to_partition_spec(spec)
            for name, spec in candidate.param_specs}


def seed_candidate(probe) -> _space.Candidate:
    """The hand-written layout (the probe step's actual param shardings)
    expressed as a Candidate, so it ranks in the same table."""
    from ..analysis.sharding_flow import spec_of

    mesh = probe.mesh
    mesh_axes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    sizes = dict(mesh_axes)
    table = []
    for name, sh in sorted(probe._p_shard.items()):
        ndim = len(probe.params[name].shape)
        spec = spec_of(sh, ndim)
        table.append((name, spec if spec is not None
                      else tuple(() for _ in range(ndim))))
    batch_axes = tuple(a for a in _space.DATA_AXES
                       if int(sizes.get(a, 1)) > 1)
    mesh_name = ".".join(f"{a}{n}" for a, n in mesh_axes if n > 1) \
        or "single"
    return _space.Candidate(name=f"{mesh_name}/seed", family="seed",
                            mesh_axes=mesh_axes,
                            param_specs=tuple(table),
                            batch_axes=batch_axes)


def _state_degrees(probe, candidate: _space.Candidate,
                   shard_axis: Optional[str]) -> Dict[str, int]:
    """Shard degree of each param's optimizer state under the candidate:
    the param's own degree, times the ZeRO axis when it is free (the
    fleet ``_state_sharding_like`` placement)."""
    sizes = candidate.axis_sizes()
    out: Dict[str, int] = {}
    for name, spec in candidate.param_specs:
        deg = _cost.shard_degree(spec, sizes)
        if shard_axis:
            z = int(sizes.get(shard_axis, 1))
            used = {a for e in (spec or ()) for a in e}
            if z > 1 and shard_axis not in used:
                shape = tuple(probe.params[name].shape)
                if any((not e) and d % z == 0 and d >= z
                       for e, d in zip(
                           (spec or tuple(() for _ in shape)), shape)):
                    deg *= z
        out[name] = deg
    return out


def _candidate_in_specs(probe, candidate: _space.Candidate, args) -> List:
    """Flat canonical arg specs for the step signature under the
    candidate — params from the table, optimizer state through the fleet
    ZeRO placement, batch over the candidate's data axes, everything
    else replicated."""
    import jax

    from ..analysis import sharding_flow as _sf

    sizes = candidate.axis_sizes()
    zero_axis = getattr(probe.optimizer, "_shard_state_axis", None) \
        or "sharding"
    specs_by_name = dict(candidate.param_specs)

    def param_spec(name: str, ndim: int):
        spec = specs_by_name.get(name)
        if spec is None:
            spec = tuple(() for _ in range(ndim))
        return tuple(spec) + tuple(() for _ in range(ndim - len(spec)))

    def state_spec(name: str, leaf) -> Tuple[Tuple[str, ...], ...]:
        # moments shaped like the param inherit its spec; anything else
        # (step counters etc.) starts replicated — then the ZeRO axis
        # takes the first free divisible dim (fleet _state_sharding_like)
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        if not shape:
            return ()
        pshape = tuple(int(d) for d in probe.params[name].shape)
        base = list(param_spec(name, len(shape))) if shape == pshape \
            else [()] * len(shape)
        z = int(sizes.get(zero_axis, 1))
        used = {a for e in base for a in e}
        if z > 1 and zero_axis not in used:
            for i, e in enumerate(base):
                if not e and shape[i] % z == 0 and shape[i] >= z:
                    base[i] = (zero_axis,)
                    break
        return tuple(base)

    batch_entry = tuple(a for a in candidate.batch_axes
                        if int(sizes.get(a, 1)) > 1)
    params, opt_state, buffers, ef, x, y, lr, seed = args[:8]

    flat: List = []
    for name in sorted(params):  # dict flatten order is sorted keys
        flat.append(param_spec(name, len(params[name].shape)))
    for name in sorted(opt_state):
        leaves = jax.tree_util.tree_leaves(opt_state[name])
        flat.extend(state_spec(name, leaf) for leaf in leaves)
    flat.extend(_sf.REPLICATED(len(getattr(leaf, "shape", ())))
                for leaf in jax.tree_util.tree_leaves(buffers))
    flat.extend(_sf.REPLICATED(len(getattr(leaf, "shape", ())))
                for leaf in jax.tree_util.tree_leaves(ef))
    for arr in (x, y):
        nd = len(arr.shape)
        flat.append(((batch_entry,) if batch_entry else ((),))
                    + tuple(() for _ in range(nd - 1)))
    flat.append(())   # lr
    flat.append(())   # seed
    if getattr(probe, "_health", False):
        import numpy as np
        flat.append(_sf.REPLICATED(np.ndim(probe._health_poison)))
    return flat


def search_train_step(model=None, optimizer=None, mesh=None,
                      batch_shape: Optional[Tuple[int, int]] = None,
                      hw: Optional[attribution.HardwareSpec] = None,
                      families: Optional[Sequence[str]] = None,
                      probe=None,
                      axis_names: Sequence[str] = _space.AXIS_NAMES,
                      fixed_mesh: bool = False,
                      ) -> SearchResult:
    """Run the full layout search for a training step. Either pass a
    ``probe`` (an existing ShardedTrainStep under the seed layout) or
    ``model``+``optimizer`` (+``mesh``) for the search to build one.

    ``fixed_mesh=True`` searches only the rule-table dimension: every
    candidate keeps the probe's mesh factorization (what the elastic
    supervisor needs — it owns the mesh, the search owns the layout)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..observability import anatomy as _anatomy

    t0 = time.perf_counter()
    if probe is None:
        if model is None or optimizer is None:
            raise ValueError("search_train_step needs a probe step or "
                             "model+optimizer")
        from ..distributed.fleet.utils import make_sharded_train_step
        probe = make_sharded_train_step(model, optimizer, mesh=mesh)
    if probe._pp > 1:
        raise ValueError("autoshard does not search pipeline layouts "
                         "(pp>1); shard the pp mesh by hand")
    if probe.scaler_state is not None:
        raise ValueError("autoshard does not model the loss-scaler step "
                         "signature; search without a scaler")

    ndev = probe.mesh.devices.size
    if batch_shape is None:
        batch_shape = (2 * ndev, 16)
    bsz, seq = int(batch_shape[0]), int(batch_shape[1])
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 16, size=(bsz, seq), dtype=np.int32))
    y = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))

    closed = probe.step_jaxpr(x, y)
    args = (probe.params, probe.opt_state, probe.buffers, probe.ef_state,
            x, y, jnp.float32(1e-3), jnp.uint32(0))

    if hw is None:
        hw = attribution.hardware_for_device(jax.devices()[0].device_kind)

    flat = _anatomy.flat_costs(closed.jaxpr)
    flat_totals = {"flops": float(flat.get("flops", 0.0)),
                   "hbm_bytes": float(flat.get("hbm_bytes", 0.0))}

    param_bytes = {
        name: int(np.prod(arr.shape, dtype=np.int64))
        * np.dtype(arr.dtype).itemsize
        for name, arr in probe.params.items()}
    state_bytes = {
        name: sum(int(np.prod(l.shape, dtype=np.int64))
                  * np.dtype(l.dtype).itemsize
                  for l in jax.tree_util.tree_leaves(probe.opt_state[name]))
        for name in probe.opt_state}
    shard_axis = getattr(probe.optimizer, "_shard_state_axis", None)

    shapes = {name: tuple(arr.shape) for name, arr in probe.params.items()}
    seed = seed_candidate(probe)
    enumerated = _space.enumerate_candidates(
        shapes, ndev, axis_names=axis_names, families=families,
        batch_divisor=bsz)
    if fixed_mesh:
        want = {a: n for a, n in seed.mesh_axes if int(n) > 1}
        enumerated = [
            c for c in enumerated
            if {a: n for a, n in c.mesh_axes if int(n) > 1} == want]
    candidates = [seed] + [c for c in enumerated
                           if c.signature() != seed.signature()]

    scored: List[RankedCandidate] = []
    rejected: List[Tuple[str, str]] = []
    for i, cand in enumerate(candidates):
        try:
            in_specs = _candidate_in_specs(probe, cand, args)
            c = _cost.score_candidate(
                closed, in_specs, cand, hw, flat_totals, param_bytes,
                state_bytes, _state_degrees(probe, cand, shard_axis),
                path=f"autoshard/{cand.name}")
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            rejected.append((cand.name, f"{type(e).__name__}: {e}"))
            continue
        if not c.fits:
            rejected.append((cand.name,
                             f"HBM fit {c.hbm_fit_bytes / 1e9:.2f} GB "
                             f"exceeds {c.hbm_capacity_bytes / 1e9:.0f} GB"))
            continue
        scored.append(RankedCandidate(candidate=cand, cost=c,
                                      is_seed=(i == 0)))

    # seed-first stable sort: ties go to the hand-written layout
    scored.sort(key=lambda rc: (
        round(rc.cost.floor_ms, 9),
        round(rc.cost.wire_bytes_per_device, 3),
        round(rc.cost.hbm_fit_bytes, 1),
        not rc.is_seed,
        rc.candidate.name))
    for r, rc in enumerate(scored):
        rc.rank = r

    dt = time.perf_counter() - t0
    result = SearchResult(
        ranked=scored, rejected=rejected, hw_name=hw.name,
        device_count=ndev, batch_shape=(bsz, seq), search_seconds=dt,
        flat_totals=flat_totals)

    _metrics.gauge("autoshard.candidates", len(scored))
    _metrics.gauge("autoshard.rejected", len(rejected))
    _metrics.histogram("autoshard.search_ms", dt * 1e3)
    if result.winner is not None:
        _metrics.gauge("autoshard.winner_floor_ms",
                       result.winner.cost.floor_ms)
        _metrics.gauge("autoshard.winner_is_seed",
                       1.0 if result.winner.is_seed else 0.0)
    return result
