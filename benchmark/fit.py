"""Compile-only fit check: what the TPU compiler says each program needs.

    JAX_PLATFORMS=cpu python3 benchmark/fit.py train gpt3-6p7b-mp4 --layers 16 --batch 8
    JAX_PLATFORMS=cpu python3 benchmark/fit.py serve gpt3-1p3b-serve --kv-pages 1400
    JAX_PLATFORMS=cpu python3 benchmark/fit.py reference gpt3-1p3b-train --batch 16

Runs in the sandbox, without a chip: programs are compiled by the installed
TPU compiler (Mosaic included) for a described ``v5e:2x2`` topology, and
``memory_analysis()`` gives the bytes on each device. Nothing runs, so this
says nothing about time. The depth and batch of gpt3-6p7b-mp4 and kv_pages of
gpt3-1p3b-serve in the configuration files are this script's output, recorded
there with the bytes. ``--layers`` / ``--batch`` / ``--kv-pages`` override
the file's values to search; without them the file is checked as it stands.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

GIB = 2.0**30
HBM_LIMIT = 15.75 * GIB   # bytes_limit a v5e chip reports (my chip run, PR 21)


def _bytes(exe):
    from harness import device

    b = device.executable_bytes(exe)
    b["need"] = b["argument"] + b["temp"] + b["output"] - b["alias"]
    return b


def _show(name, b):
    print(f"{name}: arguments {b['argument'] / GIB:.2f} GiB, temp "
          f"{b['temp'] / GIB:.2f} GiB, output {b['output'] / GIB:.2f} GiB, "
          f"aliased {b['alias'] / GIB:.2f} GiB -> needs {b['need'] / GIB:.2f}"
          f" of {HBM_LIMIT / GIB:.2f} GiB "
          f"({'fits' if b['need'] < HBM_LIMIT else 'DOES NOT FIT'})",
          flush=True)


def fit_train(cfg, traffic, layers, batch):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from harness import common
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group
    from paddle_tpu.kernels.mesh import kernel_sites
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    m, par, o = dict(cfg["model"]), cfg["parallel"], cfg["optimizer"]
    if layers:
        m["num_layers"] = layers
    B, S = batch or traffic["batch"], traffic["seq_len"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    common.init_fleet(dp_degree=par["dp_degree"], mp_degree=par["mp_degree"])
    host_mesh = get_hybrid_communicate_group().get_mesh()
    n = host_mesh.devices.size
    mesh = Mesh(np.array(topo.devices[:n]).reshape(host_mesh.devices.shape),
                host_mesh.axis_names)
    t0 = time.time()
    model = GPTForCausalLM(GPTConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        intermediate_size=m["intermediate_size"], max_seq_len=m["max_seq_len"],
        dropout=0.0, use_recompute=cfg["runner_settings"]["use_recompute"],
        loss_chunk=cfg["runner_settings"]["loss_chunk"])).astype(m["dtype"])
    opt = paddle.optimizer.AdamW(learning_rate=o["learning_rate"],
                                 parameters=model.parameters(),
                                 moment_dtype=o["moment_dtype"])
    step = make_sharded_train_step(model, opt, mesh=mesh)
    x = np.zeros((B, S), np.int32)
    exe = step.lower_compiled(x, x).compile()
    b = _bytes(exe)
    _show(f"train step {cfg['name']} L={m['num_layers']} B={B} S={S} on "
          f"{n} chip(s) [{time.time() - t0:.0f} s]", b)
    print("Mosaic calls:", kernel_sites(exe), flush=True)
    return b


def fit_serve(cfg, kv_pages, only=None):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.kernels.mesh import kernel_sites
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig
    from paddle_tpu.serving.engine import KV_DONATE_ARGNUMS

    m, e = cfg["model"], dict(cfg["engine"])
    if kv_pages:
        e["kv_pages"] = kv_pages
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = Mesh(np.array(topo.devices[:1]), ("x",))
    sh = NamedSharding(one, P())
    model = GPTForCausalLM(GPTConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        intermediate_size=m["intermediate_size"], max_seq_len=m["max_seq_len"],
        dropout=0.0)).astype(m["dtype"])
    eng = Engine(model, EngineConfig(
        max_batch_size=e["max_batch_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=tuple(e["prefill_buckets"]), page_size=e["page_size"],
        kv_pages=e["kv_pages"], prefix_cache=e["prefix_cache"],
        speculative=e["speculative"]))
    progs = [("decode", eng.decode_program())]
    progs += [(f"prefill/{b}", eng.prefill_program(b))
              for b in e["prefill_buckets"]]
    progs += [(f"extend/{b}", eng.extend_program(b))
              for b in e.get("extend_buckets", [])]
    worst = None
    if only:
        progs = [p for p in progs if p[0] in only]
    for name, (fn, args) in progs:
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
        t0 = time.time()
        with jax.set_mesh(one):
            exe = jax.jit(fn, donate_argnums=KV_DONATE_ARGNUMS).lower(
                *abstract).compile()
        b = _bytes(exe)
        _show(f"engine {name} kv_pages={e['kv_pages']} "
              f"[{time.time() - t0:.0f} s] {kernel_sites(exe)}", b)
        if worst is None or b["need"] > worst["need"]:
            worst = b
    return worst


def fit_reference(cfg, traffic, layers, batch):
    """The check's own training reference (row block + accumulate), so that
    it is known to fit beside nothing else."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from reference import gpt as ref

    m = dict(cfg["model"])
    if layers:
        m["num_layers"] = layers
    S, rows = traffic["seq_len"], cfg["check"]["rows_per_block"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = Mesh(np.array(topo.devices[:1]), ("x",))
    sh = NamedSharding(one, P())
    h, f, V, L = m["hidden_size"], m["intermediate_size"], m["vocab_size"], m["num_layers"]
    shapes = {"gpt.embeddings.word_embeddings.weight": (V, h),
              "gpt.embeddings.position_embeddings.weight": (m["max_seq_len"], h),
              "gpt.final_ln.weight": (h,), "gpt.final_ln.bias": (h,)}
    for l in range(L):
        p = f"gpt.layers.{l}."
        shapes.update({p + "ln1.weight": (h,), p + "ln1.bias": (h,),
                       p + "ln2.weight": (h,), p + "ln2.bias": (h,),
                       p + "attn.qkv.weight": (h, 3 * h), p + "attn.qkv.bias": (3 * h,),
                       p + "attn.proj.weight": (h, h), p + "attn.proj.bias": (h,),
                       p + "mlp.fc1.weight": (h, f), p + "mlp.fc1.bias": (f,),
                       p + "mlp.fc2.weight": (f, h), p + "mlp.fc2.bias": (h,)})
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=sh)
    params = {k: sds(s, jnp.float32) for k, s in shapes.items()}
    acc = {k: sds(s, jnp.float32) for k, s in shapes.items()}
    x = sds((rows, S), jnp.int32)

    def block_grad(params, acc, x, y):
        l, g = jax.value_and_grad(
            lambda q: ref.loss_sum(q, x, y, m, remat=True))(params)
        return l, {k: acc[k] + g[k] for k in acc}

    t0 = time.time()
    exe = jax.jit(block_grad, donate_argnums=(1,)).lower(params, acc, x, x).compile()
    b = _bytes(exe)
    _show(f"reference block_grad L={L} rows={rows} S={S} [{time.time() - t0:.0f} s]", b)
    return b


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("train", "serve", "reference"))
    ap.add_argument("config")
    ap.add_argument("--traffic")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--kv-pages", type=int)
    ap.add_argument("--programs", help="serve: comma-separated subset, e.g. "
                    "decode,prefill/1024,extend/256")
    a = ap.parse_args()
    from harness import common

    cfg = common.load_json("configs", a.config + ".json")
    if a.what == "serve":
        b = fit_serve(cfg, a.kv_pages, a.programs.split(',') if a.programs else None)
    else:
        traffic = common.load_json("traffic", a.traffic + ".json")
        fn = fit_train if a.what == "train" else fit_reference
        b = fn(cfg, traffic, a.layers, a.batch)
    print(json.dumps(b))


if __name__ == "__main__":
    main()
