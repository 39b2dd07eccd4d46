"""GPT causal-LM pretraining (the PaddleNLP gpt-3 example workflow:
fleet hybrid strategy -> distributed model -> train loop -> checkpoints).

Smoke (CPU): python examples/gpt_pretrain.py --smoke
TPU:         python examples/gpt_pretrain.py --hidden 2048 --layers 12 \
                 --batch 32 --steps 100
Real data:   --data 'shards/*.bin' feeds packed [B, S] batches from the
             deterministic paddle_tpu.data pipeline; with --ckpt-dir and
             --save-steps N the data position rides in the checkpoint, so
             a restarted run resumes mid-epoch on the exact next batch.
Multi-chip:  set dp/mp degrees; shardings compile through GSPMD.
Elastic:     --elastic wraps the loop in the preemption-tolerant
             supervisor (distributed.elastic): heartbeat liveness under
             --heartbeat-dir, mesh re-formation on host loss (dp shrinks,
             mp never), live reshard of the train state, data shards
             re-dealt with exactly-once coverage re-validated.
"""

import argparse
import os
import time

import numpy as np


def _log_autoshard(step, top=5):
    """Print the search's ranked table (attached by make_sharded_train_step
    when --autoshard ran)."""
    res = getattr(step, "autoshard_result", None)
    if res is None:
        return
    print(f"autoshard: {len(res.ranked)} layout(s) scored in "
          f"{res.search_seconds:.2f}s on {res.device_count} device(s)",
          flush=True)
    for rc in res.ranked[:top]:
        r = rc.row()
        print(f"  #{r['rank']} {r['layout']}"
              + (" (seed)" if r["seed"] else "")
              + f": floor {r['floor_ms']:.4f}ms ({r['binding']}-bound), "
                f"wire {r['wire_bytes_per_device']:.0f} B/dev, "
                f"hbm fit {r['hbm_fit_bytes']} B", flush=True)
    w = res.winner
    print(f"autoshard: training under "
          + ("the seed layout" if w.is_seed else f"{w.candidate.name}"),
          flush=True)


def _run_elastic(args, cfg):
    """The same pretrain loop under the elastic supervisor. The step is a
    closure over the MESH (rebuilt per re-formation); the batch is a pure
    function of the step index when synthetic, so the loss trajectory is
    identical at any world size."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import elastic as E
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import GPTForCausalLM

    on_tpu = paddle.core.place.on_tpu()

    def build_step(mesh):
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        if on_tpu:
            model = model.astype("bfloat16")
        opt = paddle.optimizer.AdamW(
            learning_rate=args.lr, parameters=model.parameters(),
            multi_precision=on_tpu,
            moment_dtype="bfloat16" if on_tpu else None)
        # --autoshard composes with --elastic: every mesh re-formation
        # rebuilds the step, so the layout is re-searched for the shrunk
        # mesh (fixed_mesh: the supervisor owns the factorization, the
        # search owns the param table)
        step = make_sharded_train_step(
            model, opt, mesh=mesh, grad_reduce=args.grad_reduce,
            accumulate_steps=args.accum or None,
            health_stats=args.health or None,
            autoshard=args.autoshard, autoshard_fixed_mesh=True)
        if args.autoshard:
            _log_autoshard(step)
        return step

    # logical hosts: contiguous blocks of the visible devices (on a real
    # fleet: one block per process); losing a block shrinks dp
    n_dev = len(jax.devices())
    n_hosts = max(1, min(args.elastic_hosts, n_dev))
    per, extra = divmod(n_dev, n_hosts)
    hosts, at = {}, 0
    for h in range(n_hosts):
        size = per + (1 if h < extra else 0)
        hosts[h] = list(range(at, at + size))
        at += size

    build_data = None
    if args.data:
        from paddle_tpu.data import build_pretrain_pipeline

        class _ElasticData:
            """Pipeline + its live iterator: reassign/set_state restart
            iteration (prefetched batches belong to the old world)."""

            def __init__(self, pi, pc):
                self.pipe = build_pretrain_pipeline(
                    args.data, args.batch, args.seq, eos_id=args.eos_id,
                    seed=0, process_index=pi, process_count=pc,
                    device_feed=False)
                self._it = iter(self.pipe)

            def reassign(self, pi, pc, peer_progress=None):
                self.pipe.reassign(pi, pc, peer_progress=peer_progress)
                self._it = iter(self.pipe)

            def get_state(self):
                return self.pipe.get_state()

            def set_state(self, state):
                self.pipe.set_state(state)
                self._it = iter(self.pipe)

            def next_tokens(self):
                return np.asarray(next(self._it)["tokens"])

        build_data = _ElasticData

    rng_cache = {}

    def next_batch(i, data):
        if data is not None:
            x = data.next_tokens()
        else:
            rng = rng_cache.setdefault(i, np.random.RandomState(1000 + i))
            x = rng.randint(0, cfg.vocab_size,
                            size=(args.batch, args.seq), dtype=np.int32)
        return x, np.roll(x, -1, axis=1)

    mgr = None
    if args.ckpt_dir:
        from paddle_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir, keep_last_n=3, async_=True)

    monitor = None
    if args.health:
        from paddle_tpu import observability
        from paddle_tpu.observability import health as obs_health

        observability.enable()
        monitor = obs_health.HealthMonitor(on_anomaly=lambda r: print(
            f"health: {r['anomaly']} at step {r['step']}"
            + (f" in {r['group']}" if r.get("group") else ""), flush=True))

    ecfg = E.ElasticConfig(
        axes={"dp": args.dp, "mp": args.mp}, hosts=hosts,
        heartbeat_dir=args.heartbeat_dir, deadline_s=args.deadline_s,
        save_every_steps=args.save_steps)
    t0 = time.perf_counter()
    try:
        with E.ElasticRunner(build_step, ecfg, next_batch=next_batch,
                             build_data=build_data,
                             checkpoint_manager=mgr,
                             health_monitor=monitor) as runner:
            losses = runner.run(args.steps)
            s = runner.summary()
    finally:
        if mgr is not None:
            mgr.wait_until_finished()
            mgr.close()
    dt = time.perf_counter() - t0
    print(f"step {args.steps - 1}: loss {losses[-1]:.4f}", flush=True)
    print(f"done: {args.steps * args.batch * args.seq / dt:.0f} tokens/sec "
          f"(elastic: {s['restarts']} restart(s), {s['steps_lost']} step(s) "
          f"lost, world {s['hosts']} host(s) x axes {s['axes']})")
    if monitor is not None:
        print(f"health: {monitor.summary()}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CPU run")
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--grad-reduce", default=None,
                    choices=["off", "fp32", "int8", "bf16"],
                    help="explicit gradient-reduction strategy "
                         "(distributed/comm_opt): fp32 = hierarchical "
                         "reduce-scatter/all-gather, int8/bf16 = quantized "
                         "wire format with error feedback; default = XLA's "
                         "implicit all-reduce. Plan preview: "
                         "tools/comm_plan.py")
    ap.add_argument("--accum", type=int, default=0,
                    help="gradient accumulation microbatches (with "
                         "--grad-reduce, reductions overlap microbatch "
                         "boundaries)")
    ap.add_argument("--save", default=None, help="checkpoint path prefix")
    ap.add_argument("--data", default=None,
                    help="token .bin shard glob (paddle_tpu.data pipeline); "
                         "synthetic random batches when unset")
    ap.add_argument("--eos-id", type=int, default=0,
                    help="document delimiter token in the .bin shards")
    ap.add_argument("--ckpt-dir", default=None,
                    help="managed checkpoint dir: auto-resumes (model, "
                         "optimizer, AND data position)")
    ap.add_argument("--save-steps", type=int, default=0,
                    help="save to --ckpt-dir every N steps")
    ap.add_argument("--autoshard", action="store_true",
                    help="search the sharding layout at startup "
                         "(paddle_tpu.autoshard): log the ranked table and "
                         "train under the winning layout; with --elastic "
                         "the search re-runs on every mesh re-formation")
    ap.add_argument("--elastic", action="store_true",
                    help="run under the preemption-tolerant supervisor "
                         "(distributed.elastic): host loss shrinks dp and "
                         "the run continues")
    ap.add_argument("--elastic-hosts", type=int, default=2,
                    help="logical hosts the devices split into (elastic "
                         "failure domains)")
    ap.add_argument("--heartbeat-dir", default=None,
                    help="shared dir for heartbeat liveness files "
                         "(elastic failure detection)")
    ap.add_argument("--deadline-s", type=float, default=5.0,
                    help="heartbeat staleness after which a host is dead")
    ap.add_argument("--health", action="store_true",
                    help="training-numerics health: in-graph per-param-group "
                         "stat pass + HealthMonitor (NaN provenance, spike "
                         "detectors, forensic anomaly capture); anomalies "
                         "print as they fire and, with --ckpt-dir, the "
                         "first one checkpoints the pre-divergence state")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.vocab, args.hidden, args.layers, args.heads = 256, 64, 2, 4
        args.seq, args.batch, args.steps = 32, 4, 3

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": args.dp, "mp_degree": args.mp}
    fleet.init(is_collective=True, strategy=strategy)

    cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, max_seq_len=args.seq, dropout=0.0,
        use_recompute=not args.smoke, recompute_interval=2, loss_chunk=0 if args.smoke else 128,
    )
    if args.elastic:
        _run_elastic(args, cfg)
        return

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    on_tpu = paddle.core.place.on_tpu()
    if on_tpu:
        model = model.astype("bfloat16")
    opt = paddle.optimizer.AdamW(
        learning_rate=args.lr, parameters=model.parameters(),
        multi_precision=on_tpu, moment_dtype="bfloat16" if on_tpu else None)
    step = make_sharded_train_step(
        model, opt, grad_reduce=args.grad_reduce,
        accumulate_steps=args.accum or None,
        health_stats=args.health or None,
        autoshard=args.autoshard)
    if args.autoshard:
        _log_autoshard(step)

    pipe = data_it = None
    if args.data:
        from paddle_tpu.data import build_pretrain_pipeline

        # per-host shard assignment + greedy packing + device feed; the
        # GSPMD step shards the fed batch over the mesh
        pipe = build_pretrain_pipeline(
            args.data, args.batch, args.seq, eos_id=args.eos_id, seed=0)
        data_it = iter(pipe)

    mgr = None
    start = 0
    if args.ckpt_dir:
        from paddle_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir, keep_last_n=3, async_=True)
        if mgr.latest_step() is not None:
            start = int(mgr.latest_step())
            tree = mgr.restore(shardings=step.checkpoint_shardings())
            step.restore_from_checkpoint(tree)
            if pipe is not None and tree.get("data_position"):
                pipe.set_state(tree["data_position"])
            print(f"resumed from step {start}"
                  + (" (data position restored)" if pipe is not None else ""))

    monitor = None
    if args.health:
        from paddle_tpu import observability
        from paddle_tpu.observability import health as obs_health

        observability.enable()

        def _ckpt_before_divergence(record):
            # detection is pipelined one step behind, so the live train
            # state is still the last pre-anomaly params — save it
            if mgr is not None:
                st = step.state_for_checkpoint()
                if pipe is not None:
                    st.data_position = pipe.get_state()
                mgr.save(int(record["step"]), st.to_tree(), force=True)
                print(f"health: pre-divergence checkpoint at step "
                      f"{record['step']}", flush=True)

        monitor = step.attach_health_monitor(obs_health.HealthMonitor(
            on_anomaly=lambda r: print(
                f"health: {r['anomaly']} at step {r['step']}"
                + (f" in {r['group']}" if r.get("group") else ""),
                flush=True),
            checkpoint_hook=_ckpt_before_divergence,
            data_position=(pipe.get_state if pipe is not None else None)))

    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    for i in range(start, args.steps):
        if data_it is not None:
            x = next(data_it)["tokens"]
            y = jnp.roll(x, -1, axis=1)
        else:
            x = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq), dtype=np.int32))
            y = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))
        loss = step(x, y)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}", flush=True)
        if mgr is not None and args.save_steps and (i + 1) % args.save_steps == 0:
            st = step.state_for_checkpoint()
            if pipe is not None:
                st.data_position = pipe.get_state()
            mgr.save(i + 1, st.to_tree(), force=True)
    if monitor is not None:
        step.health_flush()
        print(f"health: {monitor.summary()}", flush=True)
    dt = time.perf_counter() - t0
    done = max(args.steps - start, 1)
    print(f"done: {done * args.batch * args.seq / dt:.0f} tokens/sec"
          + (f", packing efficiency {pipe.packing_efficiency:.3f}"
             if pipe is not None else ""))
    if mgr is not None:
        mgr.wait_until_finished()
        mgr.close()
    if data_it is not None:
        data_it.close()

    if args.save:
        step.sync_to_model()
        paddle.save(model.state_dict(), args.save + ".pdparams")
        paddle.save(opt.state_dict(), args.save + ".pdopt")
        print(f"saved checkpoint to {args.save}.pdparams/.pdopt")


if __name__ == "__main__":
    main()
