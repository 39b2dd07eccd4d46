"""Pallas kernels under a device mesh, and a census of them in a program.

A Mosaic custom call cannot be partitioned by GSPMD: its lowering refuses a
jit that spans more than one device, and a ``shard_map`` that is manual over
only some mesh axes (jax/_src/tpu_custom_call.py). So every kernel call site
runs its ``pallas_call`` through ``shard_kernel``: a ``jax.shard_map`` over
EVERY not-yet-manual axis of the ambient mesh, with the specs the site knows
(heads on ``mp``, rows on the data axes, an optimizer update on the
parameter's own spec). The kernel body sees local shards; nothing about it
changes. The wrapper sits INSIDE each custom_vjp rule, never around it, so
autodiff never transposes the shard_map (no replication psums to get wrong).

Which kernels a compiled program ended up with is read back from its HLO by
``kernel_sites`` — the honest answer to "did the kernel survive", for
``ShardedTrainStep.kernel_sites``, ``Engine.kernel_sites`` and chip_smoke.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Sequence

import jax
from jax.sharding import PartitionSpec as P

_MANUAL = jax.sharding.AxisType.Manual


def _auto_axes(mesh) -> Dict[str, int]:
    """{axis: size} of the ambient mesh's axes that are not already manual."""
    return {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types) if t != _MANUAL}


def fit_spec(spec: P, shape: Sequence[int], axes: Dict[str, int]) -> P:
    """``spec`` cut to what ``shape`` can carry on ``axes``: axes the mesh
    lacks (or that are already manual) drop out, and a dim whose size the
    remaining axes do not divide is left replicated — the kernel then does
    redundant work on that dim instead of failing, and stays in the program.
    """
    out = []
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    for dim, entry in zip(shape, entries):
        names = () if entry is None else (
            entry if isinstance(entry, tuple) else (entry,))
        names = tuple(a for a in names if a in axes)
        if names and dim % math.prod(axes[a] for a in names) == 0:
            out.append(names if len(names) > 1 else names[0])
        else:
            out.append(None)
    return P(*out)


def shard_kernel(fn, args, in_specs, out_specs_of):
    """Call ``fn(*args)`` (a function whose body holds Mosaic calls) so that
    it is legal under the ambient mesh. ``in_specs`` are the site's wished
    specs, one per arg; ``out_specs_of(fitted_in_specs)`` returns the output
    specs (outputs are sharded the way the fitted inputs turned out).

    No ambient mesh, or a region already manual over every axis: the plain
    call. Otherwise a shard_map manual over all remaining axes."""
    axes = _auto_axes(jax.sharding.get_abstract_mesh())
    if not axes:
        return fn(*args)
    fitted = tuple(fit_spec(s, a.shape, axes) for s, a in zip(in_specs, args))
    return jax.shard_map(fn, in_specs=fitted, out_specs=out_specs_of(fitted),
                         axis_names=frozenset(axes), check_vma=False)(*args)


# A Mosaic call in HLO text: `custom_call_target="tpu_custom_call"` with
# `metadata={op_name="jit(step)/.../flash_fwd/pallas_call"}`. pallas_call
# scopes its body under its ``name=``, so the path component before
# `/pallas_call` is the kernel — possibly wrapped by the transforms it was
# traced under (`jvp(flash_fwd)`, `transpose(jvp(flash_bwd_dq))`).
_OP_NAME = re.compile(r'op_name="[^"]*?([^/"]+)/pallas_call')
_IDENT = re.compile(r"[A-Za-z_]\w*")


def kernel_sites(program) -> Dict[str, int]:
    """{kernel name: Mosaic calls} in a compiled program (anything with
    ``as_text()``, or HLO text). Names are the ``name=`` each pallas_call in
    this package carries. Empty on CPU, where kernels run interpreted (as
    plain HLO) or not at all."""
    text = program if isinstance(program, str) else program.as_text()
    out: Dict[str, int] = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        scope = _OP_NAME.search(line)
        name = _IDENT.findall(scope.group(1))[-1] if scope else "unnamed"
        out[name] = out.get(name, 0) + 1
    return dict(sorted(out.items()))


def traced_kernels(fn, *args) -> Dict[str, int]:
    """{kernel name: ``pallas_call`` equations} in the jaxpr of ``fn(*args)``:
    what ``kernel_sites`` would read off the compiled program, where there
    is no Mosaic call to read (on the CPU a kernel runs interpreted, as plain
    HLO)."""
    out: Dict[str, int] = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                name = _IDENT.findall(str(e.params["name"]))[-1]
                out[name] = out.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return dict(sorted(out.items()))
