"""Context/sequence parallelism wired into the product (SURVEY §5.7 — the
axis the reference lacks): sep axis in hybrid_configs, GPT attention under
ring/Ulysses, and the streamed-KV flash kernel at long context."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle



@pytest.fixture(autouse=True)
def _fresh_world():
    from paddle_tpu.distributed import collective, mesh, topology

    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)
    yield
    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)


def _train_gpt(sep=1, dp=1, mp=1, mode="ring", steps=2, seed=0):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import gpt_tiny

    s = fleet.DistributedStrategy()
    s.hybrid_configs = {
        "dp_degree": dp, "pp_degree": 1, "sharding_degree": 1,
        "mp_degree": mp, "sep_degree": sep,
    }
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(seed)
    m = gpt_tiny(dropout=0.0, num_layers=2, context_parallel=mode)
    o = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    st = make_sharded_train_step(m, o)
    rng = np.random.RandomState(seed)
    x = rng.randint(0, 128, size=(4, 16))
    y = np.roll(x, -1, axis=1)
    return [float(st(x, y)) for _ in range(steps)]


def test_sep_axis_in_topology():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group

    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "sep_degree": 4}
    fleet.init(is_collective=True, strategy=s)
    hcg = get_hybrid_communicate_group()
    assert hcg.get_sep_parallel_world_size() == 4
    assert "sep" in hcg.get_mesh().axis_names
    assert hcg.get_sep_parallel_group() is not None


def test_cp_degree_alias():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group

    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"cp_degree": 2}
    fleet.init(is_collective=True, strategy=s)
    assert get_hybrid_communicate_group().get_sep_parallel_world_size() == 2


def test_gpt_ring_matches_plain():
    ref = _train_gpt()
    ring = _train_gpt(sep=4, dp=2, mode="ring")
    np.testing.assert_allclose(ring, ref, rtol=2e-4, atol=2e-5)
    assert ring[-1] < ring[0]


def test_gpt_ulysses_matches_plain():
    ref = _train_gpt()
    uly = _train_gpt(sep=4, dp=2, mode="ulysses")
    np.testing.assert_allclose(uly, ref, rtol=2e-4, atol=2e-5)


def test_gpt_sep_with_mp():
    """3-axis hybrid: sep x mp x dp."""
    ref = _train_gpt()
    mix = _train_gpt(sep=2, dp=2, mp=2)
    np.testing.assert_allclose(mix, ref, rtol=2e-4, atol=2e-5)


def test_long_context_ring_8k():
    """S=8192 on the 8-device virtual mesh: each device holds a 1k shard;
    ring attention output == full attention (VERDICT round-1 done bar)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.fleet.meta_parallel.sequence_parallel import ring_attention

    n = 8
    S, B, H, D = 8192, 1, 2, 64
    mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, S, H, D).astype(np.float32) * 0.2)
    k = jnp.asarray(rs.randn(B, S, H, D).astype(np.float32) * 0.2)
    v = jnp.asarray(rs.randn(B, S, H, D).astype(np.float32) * 0.2)

    out = jax.jit(
        shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sep", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sep"), P(None, "sep"), P(None, "sep")),
            out_specs=P(None, "sep"),
            check_vma=False,
        )
    )(q, k, v)

    # reference: plain full attention
    qt = jnp.swapaxes(q, 1, 2)
    s = (qt @ jnp.swapaxes(jnp.swapaxes(k, 1, 2), -1, -2)) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    ref = jnp.swapaxes(jax.nn.softmax(s, -1) @ jnp.swapaxes(v, 1, 2), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_kernel_long_context_vmem_bounded():
    """The streamed-KV kernel compiles and matches reference at S=4096 with
    small blocks — the config whose full-S K/V BlockSpec used to blow VMEM."""
    from paddle_tpu.kernels import flash_attention as fa

    B, S, H, D = 1, 4096, 1, 64
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(B, S, H, D).astype(np.float32) * 0.2)
    qt = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    out = fa._fwd_call(qt, qt, qt, True, 1.0 / np.sqrt(D), 512, 512)[0]
    s = (qt @ jnp.swapaxes(qt, -1, -2)) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    ref = jax.nn.softmax(s, -1) @ qt
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gpt_sep_with_pp_matches_plain():
    """Context parallelism INSIDE the compiled pipeline (the pipeline region
    goes manual over sep too; ring attention runs on local seq shards):
    sep=2 x pp=2 x dp=2 training == plain."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import gpt_tiny

    def run(sep, pp, dp):
        from paddle_tpu.distributed import collective, mesh, topology

        collective.destroy_process_group()
        mesh.reset_global_mesh()
        topology.set_hybrid_communicate_group(None)
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": dp, "pp_degree": pp, "sharding_degree": 1,
                            "mp_degree": 1, "sep_degree": sep}
        fleet.init(is_collective=True, strategy=s)
        paddle.seed(0)
        m = gpt_tiny(dropout=0.0, num_layers=2, context_parallel="ring")
        o = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        st = make_sharded_train_step(m, o, accumulate_steps=2 if pp > 1 else None)
        rng = np.random.RandomState(0)
        x = rng.randint(0, 128, size=(4, 16))
        y = np.roll(x, -1, axis=1)
        return [float(st(x, y)) for _ in range(2)]

    ref = run(sep=1, pp=1, dp=1)
    mix = run(sep=2, pp=2, dp=2)
    np.testing.assert_allclose(mix, ref, rtol=2e-4, atol=2e-5)


def test_generate_greedy():
    """GPT.generate: greedy decoding extends the prefix; deterministic."""
    from paddle_tpu.models import gpt_tiny

    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    m.eval()
    x = np.random.RandomState(0).randint(0, 128, size=(2, 8))
    out = m.generate(paddle.to_tensor(x), max_new_tokens=4)
    assert out.shape == [2, 12]
    out2 = m.generate(paddle.to_tensor(x), max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(out._value), np.asarray(out2._value))
    # sampling path runs and respects shapes
    s = m.generate(paddle.to_tensor(x), max_new_tokens=3, do_sample=True, top_k=5)
    assert s.shape == [2, 11]


def test_gpt_sep_pp_local_shard_not_divisible():
    """Inside the pp+sep manual region the attention guard must use the
    ring path even when the LOCAL shard length is not divisible by sep
    (global S=8, sep=4 -> local 2): silently chunk-local attention would
    train wrong."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import gpt_tiny

    def run(sep, pp):
        from paddle_tpu.distributed import collective, mesh, topology

        collective.destroy_process_group()
        mesh.reset_global_mesh()
        topology.set_hybrid_communicate_group(None)
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 1, "pp_degree": pp, "sharding_degree": 1,
                            "mp_degree": 1, "sep_degree": sep}
        fleet.init(is_collective=True, strategy=s)
        paddle.seed(0)
        m = gpt_tiny(dropout=0.0, num_layers=2, context_parallel="ring")
        o = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        st = make_sharded_train_step(m, o, accumulate_steps=2 if pp > 1 else None)
        rng = np.random.RandomState(0)
        x = rng.randint(0, 128, size=(4, 8))  # S=8: local shard 2 under sep=4
        y = np.roll(x, -1, axis=1)
        return [float(st(x, y)) for _ in range(2)]

    ref = run(sep=1, pp=1)
    mix = run(sep=4, pp=2)
    np.testing.assert_allclose(mix, ref, rtol=2e-4, atol=2e-5)


def test_bert_pipeline_on_sep_mesh_stays_correct():
    """Models WITHOUT a context-parallel attention path must not receive
    local seq shards even when the mesh has a sep axis (the pipeline only
    goes manual over sep when the PipelineSpec opts in)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models.bert import BertConfig, BertForMaskedLM

    def run(sep, pp):
        from paddle_tpu.distributed import collective, mesh, topology

        collective.destroy_process_group()
        mesh.reset_global_mesh()
        topology.set_hybrid_communicate_group(None)
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 1, "pp_degree": pp, "sharding_degree": 1,
                            "mp_degree": 1, "sep_degree": sep}
        fleet.init(is_collective=True, strategy=s)
        paddle.seed(0)
        cfg = BertConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                         max_position_embeddings=64, dropout=0.0, attention_dropout=0.0)
        m = BertForMaskedLM(cfg)
        o = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
        st = make_sharded_train_step(m, o, accumulate_steps=2 if pp > 1 else None)
        rng = np.random.RandomState(0)
        x = rng.randint(0, 128, size=(4, 16))
        y = np.where(rng.rand(4, 16) < 0.2, x, -100)
        return [float(st(x, y)) for _ in range(2)]

    ref = run(sep=1, pp=1)
    mix = run(sep=4, pp=2)
    np.testing.assert_allclose(mix, ref, rtol=2e-4, atol=2e-5)
