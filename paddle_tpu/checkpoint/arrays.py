"""Per-host sharded array serialization (the GSPMD-native checkpoint layout).

Each process writes ONLY its addressable replica-0 shards — cooperative
multi-host saves need no cross-host data movement, just a shared filesystem
(the tensorstore/OCDBT assumption, without the dependency). A JSON manifest
records, per array: global shape, dtype, the NamedSharding it was saved
under (mesh axes/shape + PartitionSpec, informational), and per-shard-file
offsets + CRC32 checksums. Restore validates checksums and reassembles under
a caller-supplied — possibly different — mesh via
``jax.make_array_from_callback``: each device's slice is built by reading
only the saved shard files that overlap it (the memory-efficient
redistribution idea of arXiv 2112.01075, done at deserialization time), so a
save under mesh (2,2) restores onto mesh (4,), (8,), or a single host numpy
array without ever holding more than the requested slices plus the touched
shard files.

State trees are nested dicts/lists/tuples whose leaves are arrays
(jax.Array / numpy / paddle Tensor) or JSON scalars (int/float/str/bool/
None). Tuples round-trip as lists (same treedef for every consumer here).
"""

from __future__ import annotations

import json
import os
import time
import zlib
from typing import Any, Dict, Optional

import numpy as np

MANIFEST_NAME = "manifest.json"
FORMAT = "paddle_tpu.ckpt.v1"

_SEP = "/"
_ARRAY_KEY = "__array__"


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, falling back to ml_dtypes (bfloat16, fp8, ...)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _is_array_leaf(v) -> bool:
    import jax

    from ..core.tensor import Tensor

    return isinstance(v, (jax.Array, np.ndarray, np.generic, Tensor))


def _as_host_or_jax(v):
    """Unwrap Tensor; numpy scalars become 0-d arrays."""
    from ..core.tensor import Tensor

    if isinstance(v, Tensor):
        return v._value
    if isinstance(v, np.generic):
        return np.asarray(v)
    return v


def flatten_tree(state) -> Dict[str, Any]:
    """Nested containers -> {path: leaf} with '/'-joined string paths."""
    out: Dict[str, Any] = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                k = str(k)
                if _SEP in k:
                    raise ValueError(f"state key may not contain '{_SEP}': {k!r}")
                walk(f"{prefix}{_SEP}{k}" if prefix else k, v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}{_SEP}{i}" if prefix else str(i), v)
        else:
            out[prefix] = node

    walk("", state)
    return out


def _structure(state, arrays: Dict[str, Any], prefix: str = ""):
    """Nesting skeleton for the manifest: array leaves become
    {"__array__": path} markers, scalars stay inline JSON."""
    if isinstance(state, dict):
        return {str(k): _structure(v, arrays,
                                   f"{prefix}{_SEP}{k}" if prefix else str(k))
                for k, v in state.items()}
    if isinstance(state, (list, tuple)):
        return [_structure(v, arrays, f"{prefix}{_SEP}{i}" if prefix else str(i))
                for i, v in enumerate(state)]
    if _is_array_leaf(state):
        return {_ARRAY_KEY: prefix}
    if state is None or isinstance(state, (bool, int, float, str)):
        return state
    raise TypeError(
        f"unsupported checkpoint leaf at {prefix!r}: {type(state).__name__} "
        "(arrays, numbers, strings, bools, None, and nested "
        "dict/list/tuple containers are checkpointable)")


def _unstructure(node, resolve_array):
    if isinstance(node, dict):
        if _ARRAY_KEY in node and len(node) == 1:
            return resolve_array(node[_ARRAY_KEY])
        return {k: _unstructure(v, resolve_array) for k, v in node.items()}
    if isinstance(node, list):
        return [_unstructure(v, resolve_array) for v in node]
    return node


def _file_name(path: str, offsets) -> str:
    """Deterministic shard file name: offsets make cooperative multi-host
    writes collision-free (distinct shards -> distinct names; replicas of
    the same shard are written by replica 0 only)."""
    base = path.replace(_SEP, "__")
    if not offsets:
        return f"{base}.scalar.bin"
    return f"{base}.o{'_'.join(str(o) for o in offsets)}.bin"


def _sharding_desc(arr) -> Optional[dict]:
    from jax.sharding import NamedSharding, PartitionSpec

    sh = getattr(arr, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None

    def ent(e):
        if e is None:
            return None
        if e is PartitionSpec.UNCONSTRAINED:
            return "__unconstrained__"
        if isinstance(e, tuple):
            return list(e)
        return e

    return {
        "mesh_axes": list(sh.mesh.axis_names),
        "mesh_shape": [int(d) for d in sh.mesh.devices.shape],
        "spec": [ent(e) for e in sh.spec],
    }


def _index_offsets(index, shape):
    return [int(sl.start or 0) for sl in index] if index else []


def snapshot_array(arr) -> dict:
    """Device->host snapshot of this process's replica-0 shards — the ONLY
    step-blocking part of a save. Returns {"global_shape", "dtype",
    "sharding", "shards": [(offsets, host numpy)]}; the disk write
    (``write_snapshot``) can then run on a background thread against data
    the training step can no longer mutate (donated buffers included)."""
    import jax

    v = _as_host_or_jax(arr)
    shards = []
    if isinstance(v, jax.Array) and hasattr(v, "addressable_shards"):
        global_shape = tuple(int(d) for d in v.shape)
        dtype = str(v.dtype)
        sharding = _sharding_desc(v)
        for s in v.addressable_shards:
            if s.replica_id != 0:
                continue
            data = np.ascontiguousarray(np.asarray(s.data))
            # ascontiguousarray promotes 0-d shard data to (1,);
            # normalize to the extent the shard index implies
            want = tuple(
                (self_dim if sl.stop is None else sl.stop) - (sl.start or 0)
                for sl, self_dim in zip(s.index, global_shape))
            if data.shape != want:
                data = data.reshape(want)
            shards.append((_index_offsets(s.index, global_shape), data))
    else:
        host = np.asarray(v)
        # ascontiguousarray promotes 0-d to (1,); keep the true shape
        data = np.ascontiguousarray(host).reshape(host.shape)
        global_shape = data.shape
        dtype = str(data.dtype)
        sharding = None
        if jax.process_index() == 0:
            shards.append(([0] * data.ndim, data.copy()))
    return {"global_shape": [int(d) for d in global_shape], "dtype": dtype,
            "sharding": sharding, "shards": shards}


def write_snapshot(directory: str, path: str, snap: dict) -> dict:
    """Write one snapshotted array's shard files; return its manifest entry.

    Entry shards cover only what THIS process wrote — multi-process saves
    merge the per-process entries (same global metadata, concatenated shard
    lists) before publishing the manifest.
    """
    entries = []
    total = 0
    for offsets, data in snap["shards"]:
        fname = _file_name(path, offsets)
        raw = data.tobytes()
        with open(os.path.join(directory, fname), "wb") as f:
            f.write(raw)
        total += len(raw)
        entries.append({
            "file": fname,
            "offset": offsets,
            "shape": [int(d) for d in data.shape],
            "crc32": zlib.crc32(raw) & 0xFFFFFFFF,
            "bytes": len(raw),
        })
    return {
        "global_shape": snap["global_shape"],
        "dtype": snap["dtype"],
        "sharding": snap["sharding"],
        "shards": entries,
        "_bytes_written": total,  # stripped before the manifest is published
    }


def save_array(directory: str, path: str, arr) -> dict:
    """Snapshot + write in one call (the synchronous compat path)."""
    return write_snapshot(directory, path, snapshot_array(arr))


def save_tree(directory: str, state, step: Optional[int] = None,
              manifest_name: str = MANIFEST_NAME) -> dict:
    """Write every leaf of `state` under `directory` and return the manifest
    dict (the caller publishes it — the manager only after all processes
    finish, via the COMMIT protocol)."""
    os.makedirs(directory, exist_ok=True)
    flat = flatten_tree(state)
    arrays = {}
    total = 0
    for path, leaf in flat.items():
        if _is_array_leaf(leaf):
            entry = save_array(directory, path, leaf)
            total += entry.pop("_bytes_written")
            arrays[path] = entry
    manifest = {
        "format": FORMAT,
        "step": step,
        "structure": _structure(state, arrays),
        "arrays": arrays,
        "bytes_written": total,
    }
    if manifest_name:
        write_manifest(directory, manifest, manifest_name)
    return manifest


def write_manifest(directory: str, manifest: dict,
                   manifest_name: str = MANIFEST_NAME):
    tmp = os.path.join(directory, manifest_name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, os.path.join(directory, manifest_name))


def read_manifest(directory: str, manifest_name: str = MANIFEST_NAME) -> dict:
    with open(os.path.join(directory, manifest_name)) as f:
        m = json.load(f)
    if m.get("format") != FORMAT:
        raise ValueError(f"{directory}: not a {FORMAT} checkpoint "
                         f"(format={m.get('format')!r})")
    return m


def merge_manifests(parts) -> dict:
    """Union per-process manifests (same structure/metadata, disjoint shard
    lists) into the publishable one."""
    merged = None
    for part in parts:
        if merged is None:
            merged = json.loads(json.dumps(part))
            continue
        merged["bytes_written"] += part.get("bytes_written", 0)
        for path, entry in part["arrays"].items():
            if path in merged["arrays"]:
                have = {s["file"] for s in merged["arrays"][path]["shards"]}
                merged["arrays"][path]["shards"] += [
                    s for s in entry["shards"] if s["file"] not in have]
            else:
                merged["arrays"][path] = entry
    return merged


# transient-I/O policy for restore reads: a flaky network filesystem (the
# production checkpoint home) fails reads that succeed moments later, and a
# preempted run's replacement must not die on the first EIO of a 10k-shard
# restore. Counted as ckpt.restore.retries; exhaustion re-raises with the
# shard path. Tests monkeypatch these.
RESTORE_READ_RETRIES = 2         # extra attempts after the first failure
RESTORE_RETRY_BACKOFF_S = 0.05   # doubles per attempt


class _ShardReader:
    """Lazy, checksum-validating access to one array's saved shards.

    ``read_index`` materializes an arbitrary global slice by loading ONLY
    the overlapping shard files — the unit the resharding restore path
    works in. Loaded shards are cached so a restore that touches a shard
    from several target slices reads and validates it once.
    """

    def __init__(self, directory: str, path: str, entry: dict,
                 validate: bool = True):
        self.directory = directory
        self.path = path
        self.entry = entry
        self.validate = validate
        self.dtype = _np_dtype(entry["dtype"])
        self.global_shape = tuple(entry["global_shape"])
        self._cache: Dict[str, np.ndarray] = {}

    def _read_validated(self, fpath: str, shard: dict) -> bytes:
        with open(fpath, "rb") as f:
            raw = f.read()
        if self.validate:
            crc = zlib.crc32(raw) & 0xFFFFFFFF
            if crc != shard["crc32"]:
                raise IOError(
                    f"checksum mismatch for {self.path!r} shard "
                    f"{shard['file']}: manifest {shard['crc32']:#x}, "
                    f"file {crc:#x} — checkpoint is corrupt")
        return raw

    def _load(self, shard: dict) -> np.ndarray:
        data = self._cache.get(shard["file"])
        if data is not None:
            return data
        fpath = os.path.join(self.directory, shard["file"])
        retries = max(0, int(RESTORE_READ_RETRIES))
        for attempt in range(retries + 1):
            try:
                raw = self._read_validated(fpath, shard)
                break
            except (OSError, IOError) as e:
                # covers both the open/read syscall failing and a checksum
                # mismatch (a torn page-cache read heals the same way)
                if attempt == retries:
                    raise IOError(
                        f"restore of {self.path!r} failed after "
                        f"{retries + 1} attempt(s) on shard file {fpath}: "
                        f"{e}") from e
                from ..observability import metrics as _metrics

                _metrics.counter("ckpt.restore.retries")
                time.sleep(RESTORE_RETRY_BACKOFF_S * (2.0 ** attempt))
        data = np.frombuffer(raw, dtype=self.dtype).reshape(shard["shape"])
        self._cache[shard["file"]] = data
        return data

    def read_index(self, index) -> np.ndarray:
        """Assemble the global slice `index` (tuple of slices)."""
        starts = [sl.start or 0 for sl in index] if index else []
        stops = [self.global_shape[i] if sl.stop is None else sl.stop
                 for i, sl in enumerate(index)] if index else []
        shape = [b - a for a, b in zip(starts, stops)]
        out = out_filled = None  # allocate lazily: whole-shard hits copy nothing
        for shard in self.entry["shards"]:
            s_off = shard["offset"]
            s_shape = shard["shape"]
            lo = [max(a, o) for a, o in zip(starts, s_off)]
            hi = [min(b, o + n) for b, o, n in zip(stops, s_off, s_shape)]
            if any(l >= h for l, h in zip(lo, hi)):
                continue
            data = self._load(shard)
            if out is None and starts == s_off and shape == s_shape:
                return data  # exactly one whole shard file: zero-copy
            src = tuple(slice(l - o, h - o) for l, o, h in zip(lo, s_off, hi))
            if out is None:
                out = np.empty(shape, dtype=self.dtype)
                out_filled = np.zeros(shape, dtype=bool)
            dst = tuple(slice(l - a, h - a) for l, a, h in zip(lo, starts, hi))
            out[dst] = data[src]
            out_filled[dst] = True
        if out is None or not out_filled.all():
            raise IOError(
                f"checkpoint for {self.path!r} is missing shard data for "
                f"slice {index} (torn or foreign-topology save without a "
                "merged manifest?)")
        return out

    def read_full(self) -> np.ndarray:
        return self.read_index(tuple(slice(0, n) for n in self.global_shape))


def restore_array(directory: str, path: str, entry: dict, sharding=None,
                  validate: bool = True):
    """One array back: host numpy without a sharding, or a jax.Array laid
    out per `sharding` (a NamedSharding on ANY mesh — resharding happens
    here, shard-file-granular reads, no full-array host materialization
    unless the target layout requires it)."""
    reader = _ShardReader(directory, path, entry, validate=validate)
    if sharding is None:
        return reader.read_full()
    import jax

    return jax.make_array_from_callback(
        reader.global_shape, sharding, lambda idx: reader.read_index(idx))


def _live_reshard(leaf, entry: dict, sharding):
    """Planner-driven device-to-device restore of one leaf, or None when
    the live source doesn't match the checkpoint (shape/dtype drift) or
    isn't a mesh-resident jax array — the caller then reads files."""
    import jax
    from jax.sharding import NamedSharding

    from ..distributed import resharding as _resharding

    leaf = _as_host_or_jax(leaf)
    if not (isinstance(leaf, jax.Array)
            and isinstance(getattr(leaf, "sharding", None), NamedSharding)
            and isinstance(sharding, NamedSharding)):
        return None
    if (list(leaf.shape) != list(entry["global_shape"])
            or str(leaf.dtype) != entry["dtype"]):
        return None
    try:
        plan = _resharding.plan_for(leaf, sharding)
    except _resharding.Unplannable:
        return None
    return _resharding.reshard(leaf, sharding, plan=plan)


def load_tree(directory: str, shardings=None, validate: bool = True,
              manifest: Optional[dict] = None, live_state=None):
    """Restore the full state tree. `shardings` may be a flat
    {path: NamedSharding} dict or a nested tree mirroring the state (None
    leaves = host numpy).

    `live_state` (optional, same structure) supplies arrays that are still
    resident on a mesh — e.g. the pre-reconfiguration TrainState during an
    elastic topology change. Leaves found there move device-to-device
    through the resharding planner (bitwise-identical to the file path,
    no host round trip); anything missing, mismatched, or unplannable
    falls back to the shard-file reads below."""
    m = manifest if manifest is not None else read_manifest(directory)
    flat_sh: Dict[str, Any] = {}
    if shardings:
        for p, s in flatten_tree(shardings).items():
            if s is not None:
                flat_sh[p] = s
    flat_live: Dict[str, Any] = {}
    if live_state is not None:
        flat_live = flatten_tree(live_state)

    def resolve(path):
        entry = m["arrays"].get(path)
        if entry is None:
            raise KeyError(f"array {path!r} not present in checkpoint")
        sharding = flat_sh.get(path)
        if path in flat_live and sharding is not None:
            out = _live_reshard(flat_live[path], entry, sharding)
            if out is not None:
                return out
        return restore_array(directory, path, entry,
                             sharding=sharding, validate=validate)

    return _unstructure(m["structure"], resolve)
