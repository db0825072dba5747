"""Switch-MoE transformer over a (dp, ep) mesh — expert parallelism the
reference never had (SURVEY §2.7: data parallelism only; this framework
treats ep as a first-class axis).

    python examples/moe_expert_parallel.py --steps 10
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (Transformer, TransformerConfig,
                                apply_with_aux)
from horovod_tpu.parallel import make_mesh, shard_params


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--d-model", type=int, default=128)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument("--n-experts", type=int, default=4)
    args = parser.parse_args()

    hvd.init()
    n = len(jax.devices())
    ep = 2 if n % 2 == 0 else 1
    dp = n // ep
    mesh = make_mesh({"dp": dp, "ep": ep})

    cfg = TransformerConfig(
        vocab_size=512, n_layers=args.n_layers, d_model=args.d_model,
        n_heads=4, d_ff=args.d_model * 4, max_len=args.seq_len,
        dtype=jnp.float32, moe_every=2, n_experts=args.n_experts)
    model = Transformer(cfg)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 512, (4 * dp, args.seq_len)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    params = shard_params(params, mesh)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            logits, aux = apply_with_aux(model, p, tokens)
            aux = aux["load_balancing"]
            labels = jnp.roll(tokens, -1, axis=-1)
            xent = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels))
            return xent + 0.01 * aux, (xent, aux)

        (_, (xent, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, xent, aux

    for step in range(args.steps):
        params, opt_state, xent, aux = train_step(params, opt_state,
                                                  tokens)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: xent "
                  f"{float(np.asarray(jax.device_get(xent))):.4f} "
                  f"aux {float(np.asarray(jax.device_get(aux))):.4f}")
    print("MOE_EP_DONE")
    hvd.shutdown()


if __name__ == "__main__":
    main()
