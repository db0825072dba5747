"""GSPMD transformer language model over a (dp, tp, ep) mesh.

Beyond the reference's data-parallel-only scope (SURVEY §2.7): tensor
parallelism shards attention/FFN matmuls over ``tp``, switch-MoE experts
shard over ``ep``, data over ``dp``; XLA inserts the collectives over ICI.

    python examples/transformer_lm.py --dp 2 --tp 2 --ep 2   # 8 devices
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (Transformer, TransformerConfig,
                                apply_with_aux, lm_loss)
from horovod_tpu.parallel import make_mesh, shard_params


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--ep", type=int, default=2)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--d-model", type=int, default=128)
    parser.add_argument("--n-layers", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=128)
    args = parser.parse_args()

    hvd.init()
    mesh = make_mesh({"dp": args.dp, "tp": args.tp, "ep": args.ep})

    cfg = TransformerConfig(
        vocab_size=1024, n_layers=args.n_layers, d_model=args.d_model,
        n_heads=8, d_ff=args.d_model * 4, max_len=args.seq_len,
        dtype=jnp.bfloat16, moe_every=2, n_experts=max(4, args.ep * 2))
    model = Transformer(cfg)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 1024,
                                     (4 * args.dp, args.seq_len)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    params = shard_params(params, mesh)  # GSPMD sharding rules (tp/ep)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits, aux = apply_with_aux(model, p, tokens)
            # fused Pallas softmax-xent kernel on TPU
            return lm_loss(logits, tokens) + 0.01 * aux["load_balancing"]

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        if hvd.rank() == 0:
            print(f"step {i}: loss={float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
