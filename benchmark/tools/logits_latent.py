"""Logits against logits at published widths, on the chip: the engine's own
programs of a ``serve_latent`` configuration against ONE forward of the
plain reference, position by position. A builder's check beside the cell's
``correct`` (which compares what the timed window served); it claims no
speed.

    python3 benchmark/tools/logits_latent.py deepseek-v3-l5-ep16-serve \\
        [--seed N] [--document 32768] [--tail 100] [--steps 32]

Request A: a ``--document``-token document plus ``--tail`` tokens, admitted
cold (the long prefill, the expanded form over key blocks), then ``--steps``
decode steps over the latent pool (the absorbed form, the Pallas kernel),
each fed the reference-independent token the program itself put first.
Request B: the same document plus OTHER tail tokens, admitted over the
cached latents (a prefix hit: the extend program), then ``--steps`` decode
steps. Printed per request: the largest and mean |logit difference| a
position, how many positions put the reference's best token first, and how
far under the reference's best the program's token lies where not; last a
JSON line of the same.
"""

from __future__ import annotations

import argparse
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

    @jax.jit
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
                           eng.cache.table_device(), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(new)
        rows.append(np.asarray(logits[slot], np.float32))
    return req, np.stack(rows), fed


def reference_rows(c, shapes, seed, text, first):
    """Reference logits [len(text) - first, V] at positions ``first..`` of
    ``text``, layer by layer, each layer's weights made from the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import latent_weights
    from harness.run_serve_latent import ref, reference_config

    rc = reference_config(c)
    kinds = ref.ffn_kinds(rc)
    S, qb = c["engine"]["max_seq_len"], c["check"]["q_block"]
    make = lambda names: latent_weights.make(
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


def compare(name, got, want, say):
    import numpy as np

    d = np.abs(got - want)
    agree = got.argmax(-1) == want.argmax(-1)
    under = want.max(-1) - np.take_along_axis(
        want, got.argmax(-1)[:, None], 1)[:, 0]
    out = {"request": name, "positions": int(len(got)),
           "largest_abs_diff_a_position_max": float(d.max(-1).max()),
           "largest_abs_diff_a_position_median": float(np.median(d.max(-1))),
           "mean_abs_diff": float(d.mean()),
           "largest_abs_logit": float(np.abs(want).max()),
           "argmax_agrees": int(agree.sum()),
           "widest_gap_under_references_best": float(under.max())}
    say(f"{name}: " + json.dumps(out))
    say(f"{name}: per position max |dlogit| "
        + " ".join(f"{v:.3f}" for v in d.max(-1)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=20261001)
    ap.add_argument("--document", type=int, default=32768)
    ap.add_argument("--tail", type=int, default=100)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal off the chip (no device gate)")
    a = ap.parse_args(argv)

    import jax
    import numpy as np

    from harness import common, device, latent_weights
    from harness.run_serve_latent import build_engine, build_model
    from paddle_tpu.models.decoder import param_shapes

    devs = jax.devices()[:1] if a.cpu else device.gate(1)
    tag = f"[{devs[0].platform} {devs[0].device_kind}]"
    say = lambda msg: print(f"{tag} {msg}", flush=True)
    c = common.load_json("configs", a.config + ".json")
    model = build_model(c)
    shapes = param_shapes(model.cfg)
    latent_weights.compile_makers(shapes, c["initializer_range"], c["dtype"])
    for n, p in model.named_parameters():
        p._set_value_raw(latent_weights.make(
            a.seed, shapes, c["initializer_range"], c["dtype"], [n])[n])
    eng = build_engine(model, c)
    rng = np.random.RandomState(a.seed % 2**31)
    doc = rng.randint(0, c["vocab_size"], size=a.document).tolist()
    tails = [rng.randint(0, c["vocab_size"], size=a.tail).tolist()
             for _ in range(2)]
    served = []
    for name, tail in zip(("A: prefill + decode", "B: extend + decode"),
                          tails):
        t0 = time.perf_counter()
        req, rows, fed = served_rows(eng, doc + tail, a.steps)
        say(f"{name}: {len(doc) + len(tail)} prompt tokens, hit "
            f"{req.prefix_hit_blocks} pages, {a.steps} steps in "
            f"{time.perf_counter() - t0:.1f} s")
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
        out.append(compare(name, rows, want[:len(rows)], say))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
