"""``kernels/paged_attention.extend_flash`` (and ``window_extend_flash``, the
same body under a window) in interpret mode against ``extend_attend``, its
oracle: ``T`` queries a sequence behind a cached context over gathered
head-major views; and ``paged_extend_attend``, the entry that picks between
them, over pools and a table. Nothing here is a device measurement."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import pools
from paddle_tpu.kernels.tier import use_paged_attention_impl

pa = importlib.import_module("paddle_tpu.kernels.paged_attention")

D = 16
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _draw(shape, i, dtype):
    # float32 drawn explicitly: the suite runs under x64
    return jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(48), i),
                             shape, jnp.float32).astype(dtype)


def _operands(B, Hq, Hkv, T, L, dtype, d=D):
    return (_draw((B, Hq, T, d), 1, dtype), _draw((B, Hkv, L, d), 2, dtype),
            _draw((B, Hkv, L, d), 3, dtype))


def _both(q, k, v, starts, window=None, first=None):
    """(kernel, oracle) as float32 arrays; the kernel takes q pre-scaled."""
    starts = jnp.asarray(starts, jnp.int32)
    first = None if first is None else jnp.asarray(first, jnp.int32)
    qs = q * jnp.asarray(1.0 / np.sqrt(q.shape[-1]), q.dtype)
    got = pa.extend_flash(qs, k, v, starts, window, first)
    want = pa.extend_attend(q, k, v, starts, window, first)
    assert got.shape == want.shape == q.shape and got.dtype == v.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.fixture()
def small_blocks(monkeypatch):
    """Blocks of 16 queries x 32 keys and score tiles of 32 rows, so that a
    short view is walked in many blocks and a step has several chunks."""
    def install(bq=16, bk=32, rows=32):
        monkeypatch.setattr(pa, "_extend_blocks", lambda rep, T, L: (
            bq, bk if L % bk == 0 else L))
        monkeypatch.setattr(pa, "_EXTEND_CHUNK_ROWS", rows)
        pa._extend_call.clear_cache()
    yield install
    pa._extend_call.clear_cache()


# ----------------------------------------------- the grid, on small blocks

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 40], ids=["full", "window40"])
@pytest.mark.parametrize("rep", [1, 4, 16])
def test_matches_the_oracle_on_ragged_batches(small_blocks, rep, window,
                                              dtype):
    """Three sequences at start 0, mid-view and ``L - T``; under the window
    each view starts at a ``first`` of its own (0 where the context is
    shorter than the window). A step's rows go in chunks of whole heads
    (rep 4: two of them; rep 16: eight) or whole (rep 1: one head's 16)."""
    small_blocks()
    Hkv, T, L = 2, 48, 192
    q, k, v = _operands(3, Hkv * rep, Hkv, T, L, dtype)
    if window is None:
        starts, first = [0, 77, L - T], None
    else:
        first = [0, 64, 208]
        starts = [0, 64 + 50, 208 + L - T]
    got, want = _both(q, k, v, starts, window, first)
    assert np.max(np.abs(got - want)) < TOL[dtype]


@pytest.mark.parametrize("bq, rows", [(16, 32), (32, 32), (64, 32), (16, 16)])
def test_chunks_of_heads_and_of_queries(small_blocks, bq, rows):
    """A chunk of a step's score rows is several heads' queries (block 16,
    chunks of 32), one head's (32 / 32), a part of one head's (64 / 32), or
    a head each (16 / 16)."""
    small_blocks(bq, 64, rows)
    q, k, v = _operands(2, 8, 2, 64, 256, jnp.float32)
    got, want = _both(q, k, v, [5, 192])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]
    got, want = _both(q, k, v, [60 + 5, 100 + 150], 50, [60, 100])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]


@pytest.mark.parametrize("start", [0, 1, 31, 32, 100, 207, 208])
def test_every_start_from_zero_to_the_views_end(small_blocks, start):
    small_blocks()
    q, k, v = _operands(1, 4, 2, 16, 224, jnp.float32)
    got, want = _both(q, k, v, [start])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]


@pytest.mark.parametrize("T", [1, 5, 20, 33])
def test_rows_that_do_not_fill_a_sublane_tile_are_padded(small_blocks, T):
    """A verify step's k + 1 queries, a bucket no tile divides: the wrapper
    pads the queries and drops the padded rows."""
    small_blocks()
    q, k, v = _operands(2, 4, 1, T, 96, jnp.float32)
    got, want = _both(q, k, v, [3, 96 - T])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]


def test_padded_rows_past_the_views_end(small_blocks):
    """A bucket's rows behind the real tokens may stand past the view's
    last position (a draft near the end of a sequence): they see every
    key, as the oracle's do, and the real rows are untouched."""
    small_blocks()
    q, k, v = _operands(1, 4, 2, 32, 128, jnp.float32)
    got, want = _both(q, k, v, [110])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]
    got, want = _both(q, k, v, [200 + 110], 48, [200])
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]


# ------------------------------------------ the blocks the shapes choose

@pytest.mark.parametrize("rep, T, L, want", [
    (16, 128, 19456, (64, 1024)),     # the rag cell's full layer
    (16, 2048, 6144, (64, 1024)),     # ... and a sliding layer's padded view
    (16, 16, 5120, (16, 1024)),       # the reasoning cell's smallest bucket
    (8, 512, 4096, (128, 1024)),      # the agents cell
    (1, 1024, 4096, (1024, 1024)),    # the hybrid cell: one head fills a step
    (1, 3328, 4096, (256, 1024)),     # 3,328 = 13 x 256
    (1, 16, 3328, (16, 256)),         # a view 1,024 does not divide, as it is
    (2, 128, 4352, (128, 256)),       # 4,352 = 17 x 256
    (4, 48, 1152, (16, 128)),
    (4, 32, 200, (32, 200)),          # a test's view: whole
])
def test_blocks_follow_the_shapes(rep, T, L, want):
    assert pa._extend_blocks(rep, T, L) == want


@pytest.mark.parametrize("L", [2048, 1280, 1152, 200],
                         ids=["1024-divides", "256-divides", "128-divides",
                              "whole"])
@pytest.mark.parametrize("window", [None, 300], ids=["full", "window300"])
def test_the_real_blocks_on_views_1024_does_and_does_not_divide(L, window):
    """No patch: ``_extend_blocks``'s own sizes, bfloat16 as served."""
    pa._extend_call.clear_cache()
    q, k, v = _operands(2, 8, 2, 64, L, jnp.bfloat16)
    if window is None:
        got, want = _both(q, k, v, [0, L - 64])
    else:
        got, want = _both(q, k, v, [1000 + 20, 3000 + L - 64], window,
                          [1000, 3000])
    assert np.max(np.abs(got - want)) < TOL[jnp.bfloat16]


# --------------------------- what is neither fetched nor computed

@pytest.mark.parametrize("window", [None, 40], ids=["full", "window40"])
def test_blocks_no_query_sees_are_not_read(small_blocks, window):
    """Key blocks past the last query's position, and under a window those
    wholly before the first query's window, are NaN in the view: the
    output is the oracle's on the clean view, and finite."""
    small_blocks()
    T, L, bk = 32, 256, 32
    q, k, v = _operands(2, 4, 2, T, L, jnp.float32)
    starts = np.array([70, 130])
    first = None if window is None else np.array([0, 32])
    rel = starts - (0 if first is None else first)
    _, want = _both(q, k, v, starts, window, first)
    dead = np.zeros((2, L), bool)
    for b in range(2):
        dead[b, (rel[b] + T - 1) // bk * bk + bk:] = True
        if window is not None:
            dead[b, :max(rel[b] - window + 1, 0) // bk * bk] = True
    assert dead.any(axis=1).all() and (window is None or dead[1, 0])
    poison = jnp.where(jnp.asarray(dead)[:, None, :, None], jnp.nan, 0.0)
    got, _ = _both(q, k + poison, v + poison, starts, window, first)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) < TOL[jnp.float32]


def test_a_window_walks_no_more_steps_than_it_can_lie_across(small_blocks):
    """The grid's last axis under a window is the blocks a query block's
    window and its own keys can lie across, not the view's."""
    small_blocks()
    q, k, v = _operands(1, 2, 2, 16, 512, jnp.float32)
    rel = jnp.asarray([300], jnp.int32)
    text = lambda w: str(jax.make_jaxpr(lambda *a: pa._extend_call(
        *a, interpret=True, window=w))(rel, q, k, v))
    assert "grid=(1, 2, 1, 16)" in text(None)
    # window 40 + 16 queries over blocks of 32: at most three of them
    assert "grid=(1, 2, 1, 3)" in text(40)


# ------------------------------------------------- the entry, over pools

def _pools(P, Hkv, ps, dtype):
    return _draw((P, Hkv, ps, D), 5, dtype), _draw((P, Hkv, ps, D), 6, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_entry_picks_kernel_or_oracle_and_both_agree(dtype):
    """Over a shuffled table whose view (13 blocks of 8 = 104 rows) no key
    block divides: the kernel's tier widens the table with sentinels to
    whole blocks, and the trash page's rows stand behind every query."""
    ps, nb, T = 8, 13, 24
    kp, vp = _pools(41, 2, ps, dtype)
    table = jnp.asarray(np.random.RandomState(0).permutation(40)[:2 * nb]
                        .reshape(2, nb) + 1, jnp.int32)
    table = table.at[1, 9:].set(pools.PAGE_SENTINEL)
    q = _draw((2, 8, T, D), 7, dtype)
    starts = jnp.asarray([nb * ps - T, 40], jnp.int32)
    with use_paged_attention_impl("oracle"):
        want = pa.paged_extend_attend(q, kp, vp, table, starts)
    by_hand = pa.extend_attend(q, pools.paged_gather(kp, table),
                               pools.paged_gather(vp, table), starts)
    assert np.array_equal(np.asarray(want, np.float32),
                          np.asarray(by_hand, np.float32))
    with use_paged_attention_impl("pallas"):
        got = pa.paged_extend_attend(q, kp, vp, table, starts)
        text = str(jax.make_jaxpr(lambda *a: pa.paged_extend_attend(*a))(
            q, kp, vp, table, starts))
    assert "name=extend_flash" in text and "128,16]" in text   # 104 -> 128
    assert np.max(np.abs(np.asarray(got, np.float32)
                         - np.asarray(want, np.float32))) < TOL[dtype]


def test_entry_under_a_window_reads_window_blocks(small_blocks):
    """A sliding layer's extend: ``pools.window_blocks`` gives the table
    entries of the window and the new tokens and the view's first
    position; the pages behind the window are sentinels in the slot's table
    (the engine freed them) and neither tier reads them."""
    small_blocks()
    ps, window, T = 4, 16, 12
    kp, vp = _pools(64, 2, ps, jnp.float32)
    table = jnp.asarray(np.arange(1, 61).reshape(2, 30), jnp.int32)
    starts = jnp.asarray([50, 7], jnp.int32)
    first, sub = pools.window_blocks(table, starts, ps, window, T)
    q = _draw((2, 4, T, D), 8, jnp.float32)
    full = pa.extend_attend(q, pools.paged_gather(kp, table),
                            pools.paged_gather(vp, table), starts, window)
    out = {}
    for impl in ("oracle", "pallas"):
        with use_paged_attention_impl(impl):
            out[impl] = pa.paged_extend_attend(q, kp, vp, sub, starts,
                                               window=window, first=first)
            names = str(jax.make_jaxpr(lambda *a: pa.paged_extend_attend(
                *a, window=window))(q, kp, vp, sub, starts))
        assert ("name=window_extend_flash" in names) == (impl == "pallas")
        # the window's view is the whole table's under the same mask
        assert np.max(np.abs(np.asarray(out[impl]) - np.asarray(full))) < 2e-5


def test_oracle_reduces_to_decode_attend_at_one_query():
    """``extend_attend``'s window is ``decode_attend``'s at T = 1."""
    q, k, v = _operands(3, 4, 2, 1, 64, jnp.float32)
    pos = jnp.asarray([0, 20, 63], jnp.int32)
    for window in (None, 8):
        a = pa.extend_attend(q, k, v, pos, window)
        b = pa.decode_attend(q, k, v, pos, window)
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 1e-6
