"""What the v5e bring-up established, pinned on the CPU.

- the ONE device predicate (core/place.py) and interpret-only-on-cpu;
- Pallas kernels stay legal AND right under a multi-device mesh (the
  shard_kernel wrappers: numerics on the virtual CPU mesh, interpret mode);
- against a real TPU topology (compile-only, Mosaic included): every kernel
  and a tiny GPT train step lower on one device and on dp2 x mp2 — the check
  that would have caught "Mosaic kernels cannot be automatically
  partitioned" before a chip did;
- ``import paddle_tpu`` starts no backend, and the compile cache is placed
  from outside or at the fixed in-checkout path.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AbstractDevice, AbstractMesh, Mesh, NamedSharding,
                          PartitionSpec as P)

from paddle_tpu.core import place
from paddle_tpu.kernels.mesh import fit_spec, kernel_sites

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _under_device(kind):
    return jax.sharding.use_abstract_mesh(AbstractMesh(
        (1,), ("x",), abstract_device=AbstractDevice(kind, 1)))


def test_platform_predicate_reads_the_device_in_use():
    assert place.platform() == "cpu" and not place.on_tpu()
    assert place.pallas_interpret() is True  # tests interpret on cpu
    with _under_device("TPU v5 lite"):  # e.g. AOT for a TPU topology
        assert place.platform() == "tpu" and place.on_tpu()
        assert place.pallas_interpret() is False
    with _under_device("NVIDIA H100"):
        assert not place.on_tpu()
        with pytest.raises(RuntimeError, match="unsupported"):
            place.pallas_interpret()  # never silently interpreted elsewhere


def test_fit_spec_drops_what_the_shape_cannot_carry():
    axes = {"dp": 2, "mp": 2}
    assert fit_spec(P(("dp", "sharding"), None, "mp"), (4, 8, 6), axes) \
        == P("dp", None, "mp")
    assert fit_spec(P("dp", "mp"), (3, 4), axes) == P(None, "mp")  # 3 % 2
    assert fit_spec(P("pp"), (4, 4), axes) == P(None, None)        # no axis
    assert fit_spec(P(("dp", "mp")), (8,), axes) == P(("dp", "mp"))


def test_kernel_sites_reads_mosaic_calls_by_name():
    call = 'custom-call(%a), custom_call_target="tpu_custom_call", metadata='
    hlo = f'''
  %flash_fwd.3 = bf16[8]{{0}} {call}{{op_name="jit(step)/block_00/attn/flash_fwd/pallas_call" stack_frame_id=4}}
  %jvp_flash_fwd_ = bf16[8]{{0}} {call}{{op_name="jit(f)/jvp(flash_fwd)/pallas_call"}}
  %x.1 = bf16[8]{{0}} {call}{{op_name="jit(f)/transpose(jvp(flash_bwd_dq))/pallas_call"}}
  ROOT %y = f32[8]{{0}} {call}{{op_name="jit(f)/transpose(jvp())/fused_adamw/pallas_call"}}
  %other = f32[8]{{0}} custom-call(%d), custom_call_target="Sharding", metadata={{op_name="jit(f)/flash_fwd/pallas_call"}}
'''
    assert kernel_sites(hlo) == {"flash_bwd_dq": 1, "flash_fwd": 2,
                                 "fused_adamw": 1}


def _dp_mp_mesh():
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))


def test_kernels_under_a_mesh_match_unsharded():
    """dp2 x mp2 on the virtual CPU mesh (interpret mode): flash fwd+bwd,
    fused LN, fused AdamW and paged decode through their shard_map wrappers
    equal the plain single-device call."""
    from paddle_tpu.kernels.flash_attention import flash_attention_fwd
    from paddle_tpu.kernels.fused_optim import fused_adamw_update
    from paddle_tpu.kernels.norms import fused_layer_norm
    from paddle_tpu.kernels.paged_attention import paged_attention

    rng = np.random.RandomState(0)
    r = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    mesh = _dp_mp_mesh()

    def on_mesh(fn, *args):
        with jax.set_mesh(mesh):
            return jax.jit(fn)(*args)

    def same(fn, *args, tol=2e-5):
        for got, want in zip(jax.tree_util.tree_leaves(on_mesh(fn, *args)),
                             jax.tree_util.tree_leaves(fn(*args))):
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    q, k, v, do = (r(4, 128, 4, 64) for _ in range(4))

    def flash(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention_fwd(*a, causal=True),
                           q, k, v)
        return (out,) + vjp(do)

    same(flash, q, k, v, do)
    # B=3 does not divide dp: that dim falls back to replicated, still right
    same(flash, *(a[:3] for a in (q, k, v, do)))

    x, w, b = r(4, 16, 128), r(128), r(128)
    same(lambda x, w, b: fused_layer_norm(x, w, b, 1e-5), x, w, b)
    # a sequence-sharded residual stream hands its own spec (Megatron-SP)
    same(lambda x, w, b: fused_layer_norm(x, w, b, 1e-5, P("dp", "mp")),
         x, w, b)

    p, g, m, s = r(128, 256), r(128, 256), r(128, 256) * .1, abs(r(128, 256))
    same(lambda *a: fused_adamw_update(
        *a, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01,
        beta1_pow=0.9, beta2_pow=0.999, spec=P(None, "mp")), p, g, m, s)

    kp, vp = r(9, 2, 16, 64), r(9, 2, 16, 64)        # GQA 4 / 2, page 16
    table = jnp.asarray([[1, 2, -1, -1], [3, 4, 5, 6]], jnp.int32)
    same(paged_attention, r(2, 4, 1, 64), kp, vp, table,
         jnp.asarray([20, 63], jnp.int32))


_TOPOLOGY_SCRIPT = r'''
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
from paddle_tpu.kernels.mesh import kernel_sites
from paddle_tpu.models import GPTConfig, GPTForCausalLM

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
out = {"device_kind": topo.devices[0].device_kind}

# 1. each kernel at one smoke shape, single TPU device
one = Mesh(np.array(topo.devices[:1]), ("x",))
sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=NamedSharding(one, P()))

def sites(fn, *args):
    with jax.set_mesh(one):
        return kernel_sites(jax.jit(fn).lower(*args).compile())

from paddle_tpu.kernels import primitive
from paddle_tpu.kernels.flash_attention import flash_attention_fwd
from paddle_tpu.kernels.fused_optim import fused_adamw_update
from paddle_tpu.kernels.norms import fused_layer_norm, fused_rms_norm
from paddle_tpu.kernels.paged_attention import paged_attention

q = sds((2, 256, 2, 128))
out["flash"] = sites(lambda q, k, v, do: jax.vjp(
    lambda *a: flash_attention_fwd(*a, causal=True), q, k, v)[1](do), q, q, q, q)
x, w = sds((512, 256)), sds((256,))
out["layer_norm"] = sites(lambda x, w, b: fused_layer_norm(x, w, b, 1e-5), x, w, w)
out["rms_norm"] = sites(lambda x, w: fused_rms_norm(x, w, 1e-6), x, w)
p = sds((512, 256))
out["adamw"] = sites(lambda p, g, m, v: fused_adamw_update(
    p, g, m, v, lr=1e-3, beta1=.9, beta2=.999, eps=1e-8, weight_decay=.01,
    beta1_pow=.9, beta2_pow=.999), p, p, p, p)
out["paged"] = sites(paged_attention, sds((4, 4, 1, 128)), sds((17, 2, 16, 128)),
                     sds((17, 2, 16, 128)), sds((4, 4), jnp.int32), sds((4,), jnp.int32))
# the serving cells' own widths: 32 slots, 16 heads of 128, a [32, 128] table
out["paged_1p3b"] = sites(paged_attention, sds((32, 16, 1, 128)), sds((1400, 16, 16, 128)),
                          sds((1400, 16, 16, 128)), sds((32, 128), jnp.int32), sds((32,), jnp.int32))
# the hybrid cell's widths: paged_decode at 30 MHA heads over a [32, 208]
# table, and the gated delta rule's recurrent step on rows [0, 32) of a
# packed state buffer that holds 64 snapshot rows behind them
out["paged_30h"] = sites(paged_attention, sds((32, 30, 1, 128)), sds((4097, 30, 16, 128)),
                         sds((4097, 30, 16, 128)), sds((32, 208), jnp.int32), sds((32,), jnp.int32))
from paddle_tpu.kernels import gated_delta
fs = lambda *shape: sds(shape, jnp.float32)
out["gdn_step"] = sites(
    lambda *a: gated_delta._step_call(*a, interpret=False),
    fs(32, 30, 96), fs(32, 30, 96), fs(32, 30, 192), fs(32, 30), fs(32, 30),
    fs(96, *gated_delta.packed_shape(30, 96, 192)))
# the Solar share's widths: the step with a decay a KEY CHANNEL on rows
# [0, 128) of 160 (64 unpacked heads of 128 x 128), paged_decode at 64 query
# heads on 8 K/V heads over a [128, 208] table, and the grouped matmul over
# 40 HELD experts of 4,096 x 1,280 (rows of the absent 280 in no tile)
out["kda_step"] = sites(
    lambda *a: gated_delta._step_call(*a, interpret=False),
    fs(128, 64, 128), fs(128, 64, 128), fs(128, 64, 128), fs(128, 64, 128),
    fs(128, 64), fs(160, *gated_delta.packed_shape(64, 128, 128)))
out["paged_gqa"] = sites(paged_attention, sds((128, 64, 1, 128)), sds((26625, 8, 16, 128)),
                         sds((26625, 8, 16, 128)), sds((128, 208), jnp.int32), sds((128,), jnp.int32))
from paddle_tpu.kernels.grouped_matmul import grouped_matmul, plan_groups
def held(ids, x, w):
    src, dest, tg, nt, counts = plan_groups(ids, 40, 16)
    return grouped_matmul(x[src // 8], w, tg, nt, 16)
out["gmm_held"] = sites(held, sds((1024,), jnp.int32), sds((128, 4096)), sds((40, 4096, 1280)))
# the Nemotron 3 Nano share's widths: the Mamba-2 step over rows [0, 256) of
# 280 (64 heads of 64 x 128, two a packed row, 8 groups), paged_decode at 32
# query heads on 2 K/V heads over a [256, 272] table, and the grouped matmul
# over 64 HELD experts whose first matrix is kept [out, in] (1,856 x 2,688)
from paddle_tpu.kernels import mamba2
out["mamba2_step"] = sites(
    lambda *a: mamba2._step_call(*a, interpret=False),
    fs(256, 64, 64), fs(256, 64), fs(64), fs(256, 8, 128), fs(256, 8, 128),
    fs(64), fs(280, *mamba2.packed_shape(64, 128, 64)))
out["paged_32_2"] = sites(paged_attention, sds((256, 32, 1, 128)), sds((36865, 2, 16, 128)),
                          sds((36865, 2, 16, 128)), sds((256, 272), jnp.int32), sds((256,), jnp.int32))
def held_t(ids, x, w):
    src, dest, tg, nt, counts = plan_groups(ids, 64, 16)
    return grouped_matmul(x[src // 6], w, tg, nt, 16, transposed=True)
out["gmm_out_in"] = sites(held_t, sds((1536,), jnp.int32), sds((256, 2688)), sds((64, 1856, 2688)))
# the latent cell's widths: 64 slots of 128 heads over rows of 640 lanes, a
# [64, 2240] table: the absorbed decode's shared walk (tiles of 16, 4 x 128
# rows a matmul, 64 MiB of VMEM asked for)
from paddle_tpu.kernels.latent_attention import latent_paged_decode
out["latent_decode"] = sites(
    lambda q, pool, t, p: latent_paged_decode(q, pool, t, p, 512),
    sds((64, 128, 640)), sds((20481, 1, 16, 640)),
    sds((64, 2240), jnp.int32), sds((64,), jnp.int32))
# ... and the expanded form behind 33k cached tokens: 8 heads, a bucket of
# 1,024 queries over the whole 35,840-position view, steps of 1,024 x 1,024
from paddle_tpu.kernels.latent_attention import latent_flash
out["latent_flash"] = sites(
    lambda qn, qp, kn, kp, v, s: latent_flash(qn, qp, kn, kp, v, s, 8),
    sds((8, 1024, 128)), sds((8, 1024, 64)), sds((8, 35840, 128)),
    sds((1, 35840, 64)), sds((8, 35840, 128)), sds((1,), jnp.int32))
# the extends behind a cached context over gathered head-major views: the
# rag cell's full layer (128 / 8 heads, a bucket of 512 over the 19,456-row
# view) and a sliding layer's window view (window 4,096); the hybrid cell's
# smallest bucket at 30 MHA heads; the reasoning cell's view, which 1,024
# does not divide, as it is; a verify's five queries (padded to a tile)
from paddle_tpu.kernels.paged_attention import extend_flash
ext = lambda Hq, Hkv, T, L, w=None: sites(
    lambda q, k, v, s, f: extend_flash(q, k, v, s, w, f if w else None),
    sds((1, Hq, T, 128)), sds((1, Hkv, L, 128)), sds((1, Hkv, L, 128)),
    sds((1,), jnp.int32), sds((1,), jnp.int32))
out["extend_flash"] = [ext(128, 8, 512, 19456), ext(128, 8, 512, 5120, 4096),
                       ext(30, 30, 16, 4096), ext(32, 2, 128, 4352),
                       ext(16, 16, 5, 2048)]
# the decoder-hybrid-decoder's widths (Phi-4-mini-flash whole, 48 slots):
# the Mamba-1 step over rows [0, 48) of 112 (a state of 16 x 5,120 a slot),
# its scan over an extend's 1,792 tokens with two cuts, and the differential
# reads over PAIR-head pools (40 widened query heads on 10 pairs of 128
# lanes): the shared pool's decode over a [48, 968] table, a sliding layer's
# window 512 in decode and behind an extend's 1,792 new tokens
from paddle_tpu.kernels import mamba1
from paddle_tpu.kernels.paged_attention import (diff_decode_attend,
                                                diff_extend_attend)
out["mamba1_step"] = sites(
    lambda *a: mamba1._step_call(*a, interpret=False),
    fs(48, 5120), fs(48, 5120), fs(16, 5120), fs(48, 16), fs(48, 16),
    fs(5120), fs(112, 16, 5120))
out["mamba1_scan"] = sites(
    lambda *a: mamba1._scan_call(*a, interpret=False),
    fs(1, 1792, 5120), fs(1, 1792, 5120), fs(16, 5120), fs(1, 1792, 16),
    fs(1, 1792, 16), fs(5120), fs(1, 16, 5120), sds((1, 2), jnp.int32))
pair_pool = sds((3329, 10, 16, 128))
out["diff_decode"] = [
    sites(lambda q, k, v, t, p: diff_decode_attend(q, k, v, t, p, w),
          sds((48, 40, 1, 64)), pair_pool, pair_pool,
          sds((48, 968), jnp.int32), sds((48,), jnp.int32))
    for w in (None, 512)]
out["diff_extend"] = sites(
    lambda q, k, v, t, p, f: diff_extend_attend(q, k, v, t, p, 512, f),
    sds((1, 40, 1792, 64)), pair_pool, pair_pool, sds((1, 145), jnp.int32),
    sds((1,), jnp.int32), sds((1,), jnp.int32))
f32 = sds((64, 256), jnp.float32)
out["prim"] = {**sites(primitive.elementwise_kernel(lambda a, b: a + 2 * b), f32, f32),
               **sites(primitive.row_reduce_kernel(lambda acc, t: acc + t.sum(-1), 0.0), f32)}

# 2. a tiny GPT train step: one device, then dp2 x mp2 — kernels IN
def step_sites(mesh):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
                    max_seq_len=256, dropout=0.0, use_recompute=True, loss_chunk=128)
    model = GPTForCausalLM(cfg).astype("bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                                 moment_dtype="bfloat16")
    step = make_sharded_train_step(model, opt, mesh=mesh)
    x = np.zeros((8, 256), np.int32)
    return kernel_sites(step.lower_compiled(x, x).compile())

out["step_1"] = step_sites(Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "mp")))
out["step_dp2mp2"] = step_sites(Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp")))
print(json.dumps(out))
'''


def test_kernels_and_train_step_compile_for_a_tpu_topology():
    """Compile-only against ``get_topology_desc("tpu", "v5e:2x2")`` with the
    installed libtpu: the real TPU compiler, Mosaic included, no device.
    ~25 s on 8 cores (one subprocess: the topology client must not meet the
    test process's forced-CPU config)."""
    pytest.importorskip("libtpu")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", _TOPOLOGY_SCRIPT], env=env,
                       capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["device_kind"] == "TPU v5 lite"
    assert out["flash"] == {"flash_bwd_dkv": 1, "flash_bwd_dq": 1,
                            "flash_fwd": 1}
    assert out["layer_norm"] == {"layer_norm_fwd": 1}
    assert out["rms_norm"] == {"rms_norm_fwd": 1}
    assert out["adamw"] == {"fused_adamw": 1}
    assert out["paged"] == out["paged_1p3b"] == out["paged_30h"] \
        == {"paged_decode": 1}
    assert out["gdn_step"] == out["kda_step"] == {"gdn_decode_step": 1}
    assert out["paged_gqa"] == {"paged_decode": 1}
    assert out["mamba2_step"] == {"mamba2_decode_step": 1}
    assert out["paged_32_2"] == {"paged_decode": 1}
    assert out["gmm_out_in"] == {"moe_grouped_matmul": 1}
    assert out["gmm_held"] == {"moe_grouped_matmul": 1}
    assert out["latent_decode"] == {"latent_paged_decode": 1}
    assert out["latent_flash"] == {"latent_flash": 1}
    assert out["extend_flash"] == [
        {"extend_flash": 1}, {"window_extend_flash": 1}] \
        + [{"extend_flash": 1}] * 3
    assert out["mamba1_step"] == {"mamba1_decode_step": 1}
    assert out["mamba1_scan"] == {"mamba1_scan": 1}
    assert out["diff_decode"] == [{"paged_decode": 1}, {"window_decode": 1}]
    assert out["diff_extend"] == {"window_extend_flash": 1}
    assert out["prim"] == {"prim_elementwise": 1, "prim_row_reduce": 1}
    for key in ("step_1", "step_dp2mp2"):  # the mesh must not lose a kernel
        assert set(out[key]) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                                 "layer_norm_fwd", "fused_adamw"}, (key, out)
    assert out["step_dp2mp2"] == out["step_1"]


def _import_probe(env_extra):
    code = ("import json, jax, paddle_tpu, jax._src.xla_bridge as xb; "
            "print(json.dumps({'backends': sorted(xb._backends), "
            "'cache': jax.config.jax_compilation_cache_dir}))")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, **env_extra)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_import_starts_no_backend_and_places_the_compile_cache(tmp_path):
    # unset: the fixed in-checkout path (never a temp name, pid or time)
    probe = _import_probe({})
    assert probe["backends"] == []  # a launcher may import, then spawn
    assert probe["cache"] == os.path.join(REPO, ".jax_cache")
    # set from outside: the program sets no other
    probe = _import_probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert probe["backends"] == [] and probe["cache"] == str(tmp_path)


def test_chip_smoke_fails_on_a_cpu_and_its_result_line_has_exact_keys():
    """The driver's contract for chip_smoke.py: without an accelerator it
    exits non-zero and prints no result; the result line of a passing run
    holds exactly ``ok`` and ``device{platform, kind, count}``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout == "", (r.returncode, r.stdout)
    assert "needs a TPU" in r.stderr

    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    devices = jax.devices()
    line = json.loads(chip_smoke.result_line(devices))
    assert line == {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    assert isinstance(line["device"]["count"], int)
