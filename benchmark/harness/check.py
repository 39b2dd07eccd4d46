"""The comparison that decides ``correct``, and its control.

Both compare what the timed path produced with benchmark/reference/gpt.py,
which sees the seed and nothing the program made. Limits live in the
configuration file (``check``), each with the readings it was set from
(PERF.md section 2 repeats them).

Training (``train_numbers``): the compiled step object that the window
drives is first driven through its first steps by the window's own call and
feed. The reference follows the first ``reference_steps`` of them on the same
batches. Compared: each followed step's loss; the norm of the first gradient
as the optimizer got it (from its first-moment state after one step), by the
worst leaf; the norm of the parameters' change after the followed steps, by
the worst leaf. "By the worst leaf" is |program's norm - reference's norm|
over max(reference's norm of that leaf, reference's median leaf norm).

Serving (``served_gap``): for a seeded sample of the requests the window
finished (the longest among them), the reference runs once over prompt plus
served tokens; compared are the mean and the widest gap by which a served
token's logit lies below the reference's best logit at that position.

The control computes the reference with every matmul's operands rounded to
fp8 (e4m3, four significant bits): the precision below the bf16 the
configurations state. (int8 with per-row scales keeps seven bits, about what
bf16 keeps, and did not separate from the program at the test size; fp8 is
the step down.) It must come out NOT correct
(benchmark/tests/test_check_control.py at a small size; on the chip at the
cell's size by ``benchmark/control.py``).
"""

from __future__ import annotations

import functools
import sys
import time
import warnings

import numpy as np

from . import weights
from .common import BENCH

sys.path.insert(0, BENCH)
from reference import gpt as ref  # noqa: E402

# a donation the backend declines costs memory, not correctness; the list of
# buffers it prints is hundreds of lines
warnings.filterwarnings("ignore", message=".*donated buffers.*")


# ------------------------------------------------------------ the control

def mm_fp8(a, b):
    """Matmul with both operands rounded to fp8 (e4m3): four significant
    bits, where bfloat16 keeps eight. Each operand is scaled so that its
    largest magnitude sits at e4m3's largest normal (448), the usual
    per-tensor recipe, so only the mantissa's rounding matters; it is done in
    float32 arithmetic, which every backend has."""
    import jax
    import jax.numpy as jnp

    def q(x):
        x = x.astype(jnp.float32)
        m, e = jnp.frexp(x)                 # x = m * 2**e, 0.5 <= |m| < 1
        y = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
        # e4m3 has no exponent below 2**-6 relative to a top of 448 = 1.75 *
        # 2**8: flush what lies more than 2**-17 under the largest magnitude
        top = jnp.max(jnp.abs(x))
        return jnp.where(jnp.abs(x) < top * 2.0**-17, 0.0, y)

    return jnp.matmul(q(a), q(b), precision=jax.lax.Precision.HIGHEST)


MMS = {"highest": ref.mm_highest, "fp8": mm_fp8}


# --------------------------------------------------------------- training

@functools.lru_cache(maxsize=None)
def _norm_fns():
    """(norms of a tree, norms of the difference of two trees), jitted once
    per process."""
    import jax
    import jax.numpy as jnp

    l2 = lambda v: jnp.sqrt(jnp.sum(jnp.square(v)))
    f32 = lambda v: v.astype(jnp.float32)
    return (jax.jit(lambda t: {k: l2(f32(v)) for k, v in t.items()}),
            jax.jit(lambda x, y: {k: l2(f32(x[k]) - f32(y[k])) for k in x}))


def leaf_norms(tree: dict):
    """{name: L2 norm} in float32, one jitted pass."""
    return {k: float(v) for k, v in _norm_fns()[0](tree).items()}


def diff_norms(a: dict, b: dict):
    return {k: float(v) for k, v in _norm_fns()[1](a, b).items()}


def worst_leaf(got: dict, want: dict):
    """(gap, leaf): the largest |got - want| over max(want, median want)."""
    med = float(np.median(list(want.values())))
    worst, at = 0.0, None
    for k, w in want.items():
        g = abs(got[k] - w) / max(w, med, 1e-30)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, at = g, k
    if at is not None:
        at = f"{at}: {got[at]:.6g} vs {want[at]:.6g}, median {med:.6g}"
    return worst, at


def reference_train(model_cfg: dict, opt_cfg: dict, shapes: dict, seed: int,
                    batches, n_steps: int, rows_per_block: int,
                    mm_name: str = "highest", shardings=None, say=print):
    """Follow ``n_steps`` (1 or 2) optimizer steps from the seeded weights on
    ``batches`` [(x, y)], in float32, row block by row block so that it
    fits. Returns {"loss": [per step], "grad_norm": {leaf: norm of step 1's
    gradient}, "delta_norm": {leaf: norm of the parameters' change after
    n_steps}}.

    The parameters are held in float32 (their values rounded to the type the
    configuration states after each update, as the configuration keeps them
    in that type). What is kept between the steps beside them is what fits
    next to a float32 gradient: step 1's gradient in the stated type (Adam's
    moments after one step are exact functions of it: m1 = (1-b1) g1,
    v1 = (1-b2) g1^2)."""
    import jax
    import jax.numpy as jnp

    assert n_steps in (1, 2)
    mm = MMS[mm_name]
    dtype = jnp.dtype(model_cfg["dtype"])
    hp = dict(lr=opt_cfg["learning_rate"], beta1=opt_cfg["beta1"],
              beta2=opt_cfg["beta2"], eps=opt_cfg["epsilon"],
              weight_decay=opt_cfg["weight_decay"])
    p = jax.jit(lambda t: {k: v.astype(jnp.float32) for k, v in t.items()},
                donate_argnums=0, out_shardings=shardings)(
        weights.make(seed, shapes, model_cfg["initializer_range"], dtype,
                     shardings))

    def block_grad(params, acc, x, y):
        l, g = jax.value_and_grad(
            lambda q: ref.loss_sum(q, x, y, model_cfg, mm, remat=True))(params)
        return l, {k: acc[k] + g[k] for k in acc}

    block_grad = jax.jit(block_grad, donate_argnums=(1,))
    zeros = jax.jit(lambda t: {k: jnp.zeros(v.shape, jnp.float32)
                               for k, v in t.items()},
                    out_shardings=shardings)

    def full_grad(params, x, y):
        acc, total = zeros(params), 0.0
        B = x.shape[0]
        for r in range(0, B, rows_per_block):
            l, acc = block_grad(params, acc, jnp.asarray(x[r:r + rows_per_block]),
                                jnp.asarray(y[r:r + rows_per_block]))
            total += float(l)
        n = x.size
        return total / n, jax.jit(
            lambda a: {k: v / n for k, v in a.items()}, donate_argnums=0)(acc)

    # the value as the stated type would hold it. An astype round trip is
    # not enough: the TPU compiler removes convert pairs ("excess
    # precision"), and the reference would silently keep float32 parameters
    # (found on the chip: LayerNorm scales near 1.0 moved in the reference
    # and not in the program, whose bf16 cannot hold a 2e-4 step there)
    bits = {"bfloat16": (8, 7), "float16": (5, 10), "float32": (8, 23)}[dtype.name]
    stored = lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), *bits)

    def step1(params, g):  # -> p1 (float32, stated type's values), g1 (stated type)
        out_p, out_g = {}, {}
        for k in params:
            g16 = g[k].astype(dtype)  # the optimizer gets it in this type
            p1, _, _ = ref.adamw(params[k], g16.astype(jnp.float32), 0.0, 0.0,
                                 1, **hp)
            out_p[k], out_g[k] = stored(p1), g16
        return out_p, out_g

    def step2(p1, g1, g2):  # -> p2 (stored dtype)
        out = {}
        for k in p1:
            g1f = g1[k].astype(jnp.float32)
            m1 = stored((1 - hp["beta1"]) * g1f)
            v1 = stored((1 - hp["beta2"]) * g1f * g1f)
            p2, _, _ = ref.adamw(p1[k], stored(g2[k]), m1, v1, 2, **hp)
            out[k] = stored(p2)
        return out

    out = {"loss": []}
    t0 = time.perf_counter()
    x, y = batches[0]
    loss1, g = full_grad(p, x, y)
    out["loss"].append(loss1)
    out["grad_norm"] = leaf_norms({k: v.astype(dtype) for k, v in g.items()})
    say(f"reference[{mm_name}] step 1: loss {loss1:.6f} "
        f"({time.perf_counter() - t0:.1f} s)")
    p1, g1 = jax.jit(step1, donate_argnums=(0, 1))(p, g)
    del p, g
    if n_steps == 1:
        p_end = p1
    else:
        x, y = batches[1]
        loss2, g2 = full_grad(p1, x, y)
        out["loss"].append(loss2)
        say(f"reference[{mm_name}] step 2: loss {loss2:.6f} "
            f"({time.perf_counter() - t0:.1f} s)")
        p_end = jax.jit(step2, donate_argnums=(0, 1, 2))(p1, g1, g2)
        del p1, g1, g2
    p0 = weights.make(seed, shapes, model_cfg["initializer_range"], dtype,
                      shardings)
    out["delta_norm"] = diff_norms(p_end, p0)
    return out


def compare_train(run, prog: dict, want: dict, limits: dict) -> bool:
    """``prog`` and ``want`` as ``reference_train`` returns them."""
    ok = True
    for i, (a, b) in enumerate(zip(prog["loss"], want["loss"])):
        ok &= run.compare(f"loss step {i + 1}: |{a:.6f} - {b:.6f}|",
                          abs(a - b), limits["loss_abs"])
    g, at = worst_leaf(prog["grad_norm"], want["grad_norm"])
    ok &= run.compare(f"first gradient's norm, worst leaf ({at})", g,
                      limits["grad_norm_rel"])
    d, at = worst_leaf(prog["delta_norm"], want["delta_norm"])
    ok &= run.compare(f"parameters' change after {len(want['loss'])} steps, "
                      f"worst leaf ({at})", d, limits["delta_norm_rel"])
    return bool(ok)


# ---------------------------------------------------------------- serving

def pick_sample(finished, seed: int, k: int):
    """k of the finished requests, drawn from the seed, the longest (prompt
    + served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["output"])))
    rest = order[1:]
    rng = np.random.RandomState(int(seed) % (2**32))
    rng.shuffle(rest)
    return [finished[i] for i in [order[0]] + rest[:max(k - 1, 0)]]


def served_gap(model_cfg: dict, shapes: dict, seed: int, sample,
               max_answer: int = 512, control: bool = False, say=print):
    """The widest gap, over every served token of ``sample``, by which the
    token's reference logit lies below the reference's best at its position.
    With ``control`` the tokens judged are not the served ones but the ones
    an fp8 reference puts first at the same positions of the same text.
    Returns (widest gap, mean gap over the tokens, tokens compared). The
    widest gap swings by its nature (it is one near-tie); the mean is the
    steady number: a token differs from the reference's best only where the
    error of the arithmetic exceeds the margin between the two best logits,
    so the mean grows with the square of that error."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(model_cfg["dtype"])
    p = weights.make(seed, shapes, model_cfg["initializer_range"], dtype)

    def gaps(params, ids, toks, first, n):
        logits = ref.forward(params, ids[None], model_cfg)[0]   # [T, V]
        pos = first + jnp.arange(toks.shape[0])
        rows = logits[jnp.clip(pos, 0, logits.shape[0] - 1)]
        if control:
            low = ref.forward(params, ids[None], model_cfg, mm_fp8)[0]
            judged = jnp.argmax(low[jnp.clip(pos, 0, low.shape[0] - 1)], -1)
        else:
            judged = toks
        gap = rows.max(-1) - jnp.take_along_axis(rows, judged[:, None], 1)[:, 0]
        gap = jnp.where(jnp.arange(toks.shape[0]) < n, gap, 0.0)
        return gap.max(), gap.sum()

    gaps = jax.jit(gaps)
    widest, total, count = 0.0, 0.0, 0
    for r in sample:
        prompt, out = r["prompt"], r["output"]
        text = list(prompt) + list(out[:-1])
        # one shape for every request (the position table's length and the
        # longest answer): one program, compiled once and found in the cache;
        # attention is causal, so the padding after the text changes nothing
        ids = np.zeros((model_cfg["max_seq_len"],), np.int32)
        ids[:len(text)] = text
        toks = np.zeros((max(max_answer, len(out)),), np.int32)
        toks[:len(out)] = out
        g, t = gaps(p, jnp.asarray(ids), jnp.asarray(toks),
                    jnp.int32(len(prompt) - 1), jnp.int32(len(out)))
        widest, total = max(widest, float(g)), total + float(t)
        count += len(out)
    return widest, total / max(count, 1), count
