"""Does the Phi-4-mini-flash serving configuration (the WHOLE model) fit one v5e chip?
Compile-only, no chip: ``fit_solar.py`` (a copy: it names its runner) for
configurations whose runner is ``serve_phi4`` (state pools and snapshots
beside TWO page groups; the programs are the ones ``run_serve_hybrid.program_buckets``
says the traffic reaches). Besides, every program has to DONATE the state
buffers: at 5 GiB of pools and state, one undonated copy in any program ends the cell, so a
program whose aliased bytes fall short of the pools' and state buffers' is
an error here.

    python3 benchmark/fit_phi4.py phi4-mini-flash-serve \\
        [--programs decode,extend/1792,prefill/14336] [--kv-pages N]
        [--snapshots N] [--traffic reason-agent-loop-15k] [--hlo DIR]

Builds the model with zeros on the host (nothing is drawn), the engine with
its pools, and compiles every engine program the configuration names for a
described v5e chip with the TPU compiler (Mosaic included). Prints each
program's arguments / temporaries / outputs / aliased bytes and its Mosaic
calls, and last a JSON object {program: bytes} for the configuration's
``fit``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--programs")
    ap.add_argument("--kv-pages", type=int)
    ap.add_argument("--snapshots", type=int)
    ap.add_argument("--traffic", default="reason-agent-loop-15k")
    ap.add_argument("--hlo", help="directory to write each program's HLO to")
    a = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import common, device
    from harness.run_serve_phi4 import (build_engine, build_model,
                                          program_buckets)
    from paddle_tpu.kernels.mesh import kernel_sites

    cfg = common.load_json("configs", a.config + ".json")
    if a.kv_pages:
        cfg["engine"]["kv_pages"] = a.kv_pages
    if a.snapshots:
        cfg["engine"]["state_snapshots"] = a.snapshots
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = Mesh(np.array(topo.devices[:1]), ("x",))
    sh = NamedSharding(one, P())
    model = build_model(cfg)
    eng = build_engine(model, cfg)
    prefill, extend = program_buckets(
        cfg, common.load_json("traffic", a.traffic + ".json"))
    progs = {"decode": eng.decode_program}
    progs.update({f"extend/{b}": (lambda b=b: eng.extend_program(b))
                  for b in extend})
    progs.update({f"prefill/{b}": (lambda b=b: eng.prefill_program(b))
                  for b in prefill})
    only = a.programs.split(",") if a.programs else list(progs)
    out = {}
    held = eng.cache.nbytes     # the pages, the slots' state, the snapshots
    for name in only:
        fn, args = progs[name]()
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), args)
        t0 = time.time()
        with jax.set_mesh(one):
            exe = jax.jit(fn, donate_argnums=eng.donate_argnums_of(
                name.split("/")[0])).lower(*abstract).compile()
        if a.hlo:
            os.makedirs(a.hlo, exist_ok=True)
            with open(os.path.join(a.hlo, name.replace("/", "_") + ".txt"),
                      "w") as f:
                f.write(exe.as_text())
        b = device.executable_bytes(exe)
        b["need"] = b["argument"] + b["temp"] + b["output"] - b["alias"]
        out[name] = b
        if b["alias"] < held:
            raise SystemExit(
                f"{name}: {b['alias']} bytes aliased, the pools and state "
                f"buffers hold {held}: some buffer is copied, not donated")
        print(f"{name} [{time.time() - t0:.0f} s] {kernel_sites(exe)} "
              + " ".join(f"{k} {v / 2**30:.2f} GiB" for k, v in b.items()),
              flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
