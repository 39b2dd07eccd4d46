"""Does a description-built decoder's serving configuration fit one v5e chip?
Compile-only, no chip: ``fit.py``'s serving half for configurations whose
runner is ``serve_decoder`` (``fit.py`` builds ``GPTForCausalLM``).

    python3 benchmark/fit_decoder.py keye-vl2-30b-a3b-l6-serve \\
        [--programs decode,extend/128,prefill/34816] [--kv-pages N]

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
    a = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import common, device
    from harness.run_serve_decoder import build_engine, build_model
    from paddle_tpu.kernels.mesh import kernel_sites

    cfg = common.load_json("configs", a.config + ".json")
    if a.kv_pages:
        cfg["engine"]["kv_pages"] = a.kv_pages
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = Mesh(np.array(topo.devices[:1]), ("x",))
    sh = NamedSharding(one, P())
    model = build_model(cfg)
    eng = build_engine(model, cfg)
    buckets = cfg["engine"]["prefill_buckets"]
    progs = {"decode": eng.decode_program}
    progs.update({f"extend/{b}": (lambda b=b: eng.extend_program(b))
                  for b in buckets[:-1]})
    progs.update({f"prefill/{b}": (lambda b=b: eng.prefill_program(b))
                  for b in buckets[-1:]})
    only = a.programs.split(",") if a.programs else list(progs)
    out = {}
    for name in only:
        fn, args = progs[name]()
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), args)
        t0 = time.time()
        with jax.set_mesh(one):
            exe = jax.jit(fn, donate_argnums=eng.donate_argnums).lower(
                *abstract).compile()
        b = device.executable_bytes(exe)
        b["need"] = b["argument"] + b["temp"] + b["output"] - b["alias"]
        out[name] = b
        print(f"{name} [{time.time() - t0:.0f} s] {kernel_sites(exe)} "
              + " ".join(f"{k} {v / 2**30:.2f} GiB" for k, v in b.items()),
              flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
