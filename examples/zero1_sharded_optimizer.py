"""ZeRO-1 training: sharded weight update over the data-parallel mesh.

Demonstrates ``hvd.ShardedDistributedOptimizer`` (arXiv:2004.13336 —
cross-replica sharding of the weight update): per step, gradients
reduce-scatter so each replica receives one reduced 1/N shard, Adam
runs on that shard only (optimizer state is 1/N per replica), and the
update shards all-gather back.  Compare the printed per-replica state
size against the replicated baseline.

    python examples/zero1_sharded_optimizer.py
    hvdrun -np 2 python examples/zero1_sharded_optimizer.py
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import MLP
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel._compat import shard_map


def parse_args():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch-size", type=int, default=512)
    parser.add_argument("--hidden", type=int, default=256)
    parser.add_argument("--lr", type=float, default=1e-2)
    return parser.parse_args()


def main():
    args = parse_args()
    hvd.init()
    n = len(jax.devices())
    mesh = make_mesh({"hvd": n})
    batch = args.batch_size - args.batch_size % n or n

    model = MLP(features=(args.hidden, args.hidden, 8))
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 32).astype(np.float32)
    y = rng.randn(batch, 8).astype(np.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 32)))
    n_params = sum(p.size for p in jax.tree.leaves(params))

    opt = hvd.ShardedDistributedOptimizer(optax.adam(args.lr),
                                          axis_name="hvd")

    def init_fn(p):
        return hvd.sharded_state_wrap(opt.init(p))

    def step(p, s, xb, yb):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((model.apply(p, xb) - yb) ** 2))(p)
        updates, s2 = opt.update(grads, hvd.sharded_state_unwrap(s), p)
        return optax.apply_updates(p, updates), \
            hvd.sharded_state_wrap(s2), jax.lax.pmean(loss, "hvd")

    init_j = jax.jit(shard_map(
        init_fn, mesh=mesh, in_specs=P(), out_specs=P("hvd")))
    step_j = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("hvd"), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"), P())))

    state = init_j(params)
    sharded = NamedSharding(mesh, P("hvd"))
    xd, yd = jax.device_put(x, sharded), jax.device_put(y, sharded)

    for s in range(args.steps):
        params, state, loss = step_j(params, state, xd, yd)
        if hvd.rank() == 0 and s % 10 == 0:
            print(f"step {s}: loss {float(loss):.4f}")

    if hvd.rank() == 0:
        chunk = hvd.shard_chunk_size(n_params, n)
        adam_replicated = 2 * n_params
        adam_sharded = 2 * chunk
        print(f"model params: {n_params}")
        print(f"Adam state per replica: {adam_sharded} floats "
              f"(replicated baseline: {adam_replicated}) — "
              f"{adam_replicated / adam_sharded:.1f}x smaller")
    print("ZERO1 DONE")
    hvd.shutdown()


if __name__ == "__main__":
    main()
