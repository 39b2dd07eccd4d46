"""Logits against logits at published widths, on the chip: the engine's own
programs of a ``serve_mamba`` configuration against ONE token-by-token
forward of the plain reference, position by position. A builder's check
beside the cell's ``correct`` (which compares what the timed window
served); it claims no speed.

    python3 benchmark/tools/logits_mamba.py nemotron3-nano-30b-l13-ep2-serve \\
        [--seed N] [--system 2048] [--tail 464] [--steps 512]
        [--plant bf16_state|no_skip]

Request A: a ``--system``-token prompt plus ``--tail`` tokens, admitted cold
(a 2.5k prefill: the chunked recurrence from a zero state in the six
Mamba-2 layers, flash in the two attention layers), then 32 decode steps
(the recurrent step's kernel over the slot's state, the paged attend),
each fed the reference-independent token the program itself put first.
Request B: the same system prompt plus OTHER tail tokens: A left no
snapshot at the system prompt's last page, so B runs it again and takes the
branch snapshot there. Request C: a third tail, admitted behind the
RESTORED branch snapshot (the extend program: the chunked recurrence from a
given state and convolution tail), then ``--steps`` decode steps. Printed
per request: the largest and mean |logit difference| a position, how many
positions put the reference's best token first, and how far under the
reference's best the program's token lies where not; last a JSON line of
the same.

TOLERANCE, and what it was READ to see: bfloat16 weights and activations
against a float32 "highest" reference over 13 layers, the recurrent state
float32 on both sides, give logits of magnitude up to 6.5 whose largest
difference a position has a MEDIAN of 0.18 / 0.25 / 0.16 over the three
requests and reaches 2.0-2.9 at a few (my chip run, PR 46; 73-85% of the
positions put the reference's best token first, the others lie up to 1.12
under it). That is three to five times what the four-layer configurations
read. The cause offered, NOT proven at published widths (no flips were
counted there; a bfloat16 study at small widths on the CPU points to it):
five expert layers choose 6 of 128 by sigmoid scores, a near-tie flips
under bfloat16, an ungated relu² expert at weight 2.5 / 6 moves the
token's hidden state, and the state-space layers behind it carry that on
to LATER positions (a model of attention alone keeps it to the one token).
So the tool holds the MEDIAN to ``--tolerance`` (default 0.4, 1.6 times the
largest sound reading), allows at most a fifth of the positions above four
times that, and wants six positions in ten to put the reference's best
token first and none further than 2.5 under it; it exits 1 otherwise.

``--plant`` puts a fault into the PROGRAM and leaves the reference alone,
to read what that tolerance catches (my chip run, PR 46, seed 20261004):

- ``no_skip`` (``D`` = 0): CAUGHT. Medians 5.83 / 5.81 / 5.78, no position
  of 33 / 33 / 513 puts the reference's best token first, and the cell's
  own statistic (the mean gap of the program's token under the reference's
  best, limit 0.12) reads 3.66 / 3.33 / 3.59. A fault that moves every
  position by the size of the logits is seen.
- ``bf16_state`` (the state kept as bfloat16 values, arithmetic float32):
  NOT caught. Medians 0.226 / 0.098 / 0.174, 29 / 28 / 423 positions agree,
  mean gap 0.0515 / 0.0400 / 0.0417: another draw of the same noise. A
  rounding of 2^-9 a step is 0.3% of the state at the median head beside
  bfloat16 activations' 0.4% an op.

What was NOT planted at this size (the norm before the gate, a gated
expert, the router's bias let into the weights) is held by
tests/test_mamba_serving.py alone, as the bfloat16 state is: in float32 on
the CPU, ``test_the_comparison_can_fail`` plants each and reads more than
ten times ITS tolerance of 1e-4. Rotary positions on the attention layers
are planted nowhere: ``test_layers_against_the_reference`` holds the ``*``
layer to a reference that has none, at 1e-4.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def served_rows(eng, prompt, steps):
    """Admit ``prompt`` through the engine's own admission, then decode
    ``steps`` tokens greedily through ``decode_step`` over the engine's
    pools: (the request, logits [1 + steps, V] float32, the tokens fed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import SamplingParams

    rows, run = [], eng._run_prompt

    def keep(*a):
        out = run(*a)
        rows.append(np.asarray(out[0], np.float32).reshape(-1))
        return out

    eng._run_prompt = keep
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=steps + 8))
    assert eng._admit() == 1
    eng._run_prompt = run
    rows = rows[-1:]
    B, slot, m = eng.config.max_batch_size, req.slot, eng.model

    # (the pools are donated: 4.5 GiB of them beside 7.3 of weights leave no
    # room for a second copy)
    @functools.partial(jax.jit, donate_argnums=2)
    def step(params, tokens, pools, table, pos):
        (logits, new, _), _ = m.functional_call(
            params, {}, tokens, eng.cache.layer_entries(pools, table), pos,
            method="decode_step")
        return logits._value, [tuple(t._value for t in layer)
                               for layer in new]

    fed = []
    for j in range(steps):
        tok = int(rows[-1].argmax())
        fed.append(tok)
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[slot], pos[slot] = tok, len(prompt) + j
        eng._positions[slot] = pos[slot]
        eng._grow_pages()
        logits, new = step(eng.params, jnp.asarray(tokens), eng.cache.pools,
                           eng.cache.tables_device(), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(new)
        rows.append(np.asarray(logits[slot], np.float32))
    return req, np.stack(rows), fed


def plant_bf16_state():
    """``--plant bf16_state``, the witness that says what the tolerance
    sees: the PROGRAM keeps its recurrent state as bfloat16 values (every
    state a decode step or an admission writes is rounded to 8 bits of
    mantissa; the arithmetic stays float32, the mildest reading of "a
    bfloat16 state"), the reference stays as it is. ``reduce_precision``,
    not a pair of casts, which the compiler may drop."""
    from jax import lax

    from paddle_tpu.kernels import mamba2 as ssm

    low = lambda S: lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
    step, pack = ssm.mamba2_step, ssm.pack_state

    def mamba2_step(*a):
        y, S = step(*a)
        return y, low(S)

    ssm.mamba2_step = mamba2_step
    ssm.pack_state = lambda S: low(pack(S))


def reference_rows(c, shapes, seed, text, first):
    """Reference logits [len(text) - first, V] at positions ``first..`` of
    ``text``, layer by layer, each layer's weights made from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import mamba_weights
    from harness.run_serve_mamba import ref, reference_config

    rc = reference_config(c)
    kinds = rc["layer_types"]
    S, qb = c["engine"]["max_seq_len"], c["check"]["q_block"]
    make = lambda names: mamba_weights.make(
        seed, shapes, c["initializer_range"], c["dtype"], names)
    top = make(["embed.weight", "final_norm.weight", "head.weight"])
    ids = np.zeros((S,), np.int32)
    ids[:len(text)] = text
    x = jax.jit(ref.embed)(jnp.asarray(ids), top["embed.weight"])
    R = -(-(len(text) - first) // qb) * qb
    rows = jnp.clip(first + jnp.arange(R), 0, S - 1)
    for l, kind in enumerate(kinds):
        pre = f"layers.{l}."
        p = {n[len(pre):]: v for n, v in
             make([n for n in shapes if n.startswith(pre)]).items()}
        if l < len(kinds) - 1:
            x = jax.jit(lambda x, p, kind=kind: ref.layer(
                x, p, kind, rc, ref.mm_highest, qb), donate_argnums=0)(x, p)
        else:
            x = jax.jit(lambda x, p, rows, kind=kind: ref.layer(
                x, p, kind, rc, ref.mm_highest, qb, rows))(x, p, rows)
        del p
    lg = jax.jit(lambda x: ref.logits(
        x, jnp.arange(R), top["final_norm.weight"], top["head.weight"], rc))(x)
    return np.asarray(lg)[:len(text) - first]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=20261004)
    ap.add_argument("--system", type=int, default=2048)
    ap.add_argument("--tolerance", type=float, default=0.4)
    ap.add_argument("--tail", type=int, default=464)
    ap.add_argument("--steps", type=int, default=512)
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal off the chip (no device gate)")
    ap.add_argument("--plant", choices=["bf16_state", "no_skip"],
                    help="a fault put into the PROGRAM, to read what the "
                         "tolerance catches: its recurrent state kept as "
                         "bfloat16 values, or the skip D x left out")
    a = ap.parse_args(argv)

    import jax
    import numpy as np

    from harness import common, device, mamba_weights
    from tools.logits_window import compare
    from harness.run_serve_mamba import build_engine, build_model
    from paddle_tpu.models.decoder import param_shapes

    if a.plant == "bf16_state":
        plant_bf16_state()

    devs = jax.devices()[:1] if a.cpu else device.gate(1)
    tag = f"[{devs[0].platform} {devs[0].device_kind}]"
    say = lambda msg: print(f"{tag} {msg}", flush=True)
    c = common.load_json("configs", a.config + ".json")
    model = build_model(c)
    shapes = param_shapes(model.cfg)
    mamba_weights.compile_makers(shapes, c["initializer_range"], c["dtype"])
    for n, p in model.named_parameters():
        w = mamba_weights.make(
            a.seed, shapes, c["initializer_range"], c["dtype"], [n])[n]
        if a.plant == "no_skip" and n.endswith(".D"):
            w = w * 0           # the program's alone: the reference keeps D
        p._set_value_raw(w)
    eng = build_engine(model, c)
    rng = np.random.RandomState(a.seed % 2**31)
    doc = rng.randint(0, c["vocab_size"], size=a.system).tolist()
    tails = [rng.randint(0, c["vocab_size"], size=a.tail - 16 * i).tolist()
             for i in range(3)]
    served = []
    for name, tail, steps in zip(("A: cold prefill + decode",
                                  "B: prefill run again + decode",
                                  "C: restored snapshot + extend + decode"),
                                 tails, (32, 32, a.steps)):
        t0 = time.perf_counter()
        req, rows, fed = served_rows(eng, doc + tail, steps)
        say(f"{name}: {len(doc) + len(tail)} prompt tokens, resumed behind "
            f"{req.prefix_hit_blocks} pages, {steps} steps in "
            f"{time.perf_counter() - t0:.1f} s; "
            f"{eng.snapshot_alloc.num_allocated} snapshots held")
        served.append((name, doc + tail, rows, fed))
        eng._finish(req, "length")      # its slot and its own pages go back
    sites = {"/".join(map(str, k)): v for k, v in eng.kernel_sites.items()}
    say(f"engine programs and their Mosaic calls: {sites}")
    del eng, model
    import gc
    gc.collect()
    out = []
    for name, prompt, rows, fed in served:
        t0 = time.perf_counter()
        text = prompt + fed
        want = reference_rows(c, shapes, a.seed, text, len(prompt) - 1)
        say(f"{name}: reference over {len(text)} tokens in "
            f"{time.perf_counter() - t0:.1f} s")
        want = want[:len(rows)]
        out.append(compare(name, rows, want, say, a.tolerance))
        # what the cell's ``correct`` reads of these tokens: how far the
        # token the program put first lies under the reference's best
        under = want.max(-1) - np.take_along_axis(
            want, rows.argmax(-1)[:, None], 1)[:, 0]
        out[-1]["mean_gap_under_references_best"] = float(under.mean())
        say(f"{name}: mean gap of the program's token under the reference's "
            f"best = {under.mean():.4f} (the cell's limit on it: "
            f"{c['check']['limits']['served_gap_mean']})")
    print(json.dumps(out), flush=True)
    bad = [o["request"] for o in out
           if o["largest_abs_diff_a_position_median"] > a.tolerance
           or o["positions_over_4_tolerances"] > 0.2 * o["positions"]
           or o["argmax_agrees"] < 0.6 * o["positions"]
           or o["widest_gap_under_references_best"] > 2.5]
    say((f"planted {a.plant}: " if a.plant else "")
        + f"tolerance {a.tolerance} (median of the largest difference a "
        "position): " + ("held" if not bad else f"PASSED by {bad}"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
