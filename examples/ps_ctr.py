"""CTR-style training over parameter servers (the fleet PS-mode workflow —
BASELINE's brpc-PS analog): sparse features live in native PS tables; the
DEFAULT path keeps the embedding math device-resident (SparseCore-style):
touched rows are pulled ONCE per step into a [U, D] device block, the
lookup is a device gather inside the jitted step (backward = XLA
scatter-add producing the row-grad block), and the block's grads are
pushed back at the step boundary. --host-emb keeps the legacy host-side
numpy embedding arithmetic.

Smoke (local cluster in one process): python examples/ps_ctr.py --smoke
Real deployment: paddle.distributed.launch --run_mode ps (the controller
sets TRAINING_ROLE=PSERVER/TRAINER and PADDLE_PSERVER_ENDPOINTS).
"""

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--emb-dim", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--host-emb", action="store_true",
                    help="legacy host-side embedding arithmetic")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed import ps

    role = os.environ.get("TRAINING_ROLE", "LOCAL")
    if role == "PSERVER":
        ps.init_server()
        ps.run_server()
        return

    if role == "TRAINER":
        client = ps.init_worker()
        servers = []
    else:  # LOCAL: spin a cluster inside this process
        servers = [ps.PsServer("127.0.0.1:0").start() for _ in range(args.servers)]
        client = ps.PsClient([s.endpoint for s in servers])

    client.create_table(0, dim=args.emb_dim, init_range=0.05, seed=0)

    # dense tower: emb-sum -> MLP -> logit
    paddle.seed(0)
    tower = paddle.nn.Sequential(
        paddle.nn.Linear(args.emb_dim, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=tower.parameters())

    rng = np.random.RandomState(0)
    # synthetic CTR: click iff any feature id is even
    if args.host_emb:
        for step in range(args.steps):
            ids = rng.randint(0, args.vocab, size=(16, 4)).astype(np.int64)
            y = (ids % 2 == 0).any(axis=1).astype(np.float32)
            flat = ids.reshape(-1)
            rows = client.pull_sparse(0, flat)  # [16*4, D] host pull
            emb = paddle.to_tensor(rows.reshape(16, 4, args.emb_dim).sum(axis=1))
            emb.stop_gradient = False
            logit = tower(emb)[:, 0]
            loss = paddle.nn.functional.binary_cross_entropy_with_logits(
                logit, paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            # sparse grad: d(loss)/d(emb) broadcast back over the 4 summed slots
            gemb = emb.grad.numpy()  # [16, D]
            grows = np.repeat(gemb[:, None, :], 4, axis=1).reshape(-1, args.emb_dim)
            client.push_sparse(0, flat, grows, rule="adagrad", lr=0.05)
            if step % 20 == 0 or step == args.steps - 1:
                print(f"step {step}: loss {float(loss.numpy()):.4f}", flush=True)
    else:
        # device-resident path: gather + backward scatter live in the jit,
        # PS sync only at step boundaries
        from paddle_tpu.core.tensor import Tensor

        emb_table = ps.DeviceSparseEmbedding(client, 0, args.emb_dim,
                                             rule="adagrad", lr=0.05)
        params0, buffers0 = tower.functional_state()
        opt_state = opt.init_state_pytree(params0)

        @jax.jit
        def fused_step(params, opt_state, rows, local, y):
            def loss_fn(p, r):
                with paddle.no_grad():
                    emb = ps.embedding_lookup(r, local).sum(axis=1)
                    out, _ = tower.functional_call(p, buffers0, Tensor(emb))
                    loss = paddle.nn.functional.binary_cross_entropy_with_logits(
                        out[:, 0], Tensor(y))
                return loss._value.astype(jnp.float32)

            loss, (d_p, d_rows) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params, rows)
            params, opt_state = opt.apply_gradients(params, d_p, opt_state,
                                                    lr=0.01)
            return params, opt_state, loss, d_rows

        params = params0
        for step in range(args.steps):
            ids = rng.randint(0, args.vocab, size=(16, 4)).astype(np.int64)
            y = (ids % 2 == 0).any(axis=1).astype(np.float32)
            rows, local = emb_table.pull(ids)
            params, opt_state, loss, d_rows = fused_step(
                params, opt_state, rows, local, jnp.asarray(y))
            emb_table.push(d_rows)
            if step % 20 == 0 or step == args.steps - 1:
                print(f"step {step}: loss {float(loss):.4f}", flush=True)

    print(f"table rows touched: {client.table_size(0)}")
    if servers:
        client.shutdown_servers()
    print("done")


if __name__ == "__main__":
    main()
