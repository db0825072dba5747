"""Long-context attention via sequence parallelism.

Absent from the reference (SURVEY §5 "long-context: absent — design
fresh").  Two strategies over the ``sp`` mesh axis:

- ring attention: K/V blocks rotate around the ICI ring (``ppermute``)
  with online-softmax accumulation — sequence length per device stays
  T/P, memory is O(T/P * block).
- Ulysses: two ``all_to_all``s re-shard sequence -> heads so each device
  runs exact full-sequence attention on H/P heads.
- zigzag: load-balanced causal ring — each rank holds one early and
  one late chunk, so every hop costs the same two unmasked block
  attends on every rank (~2x causal throughput at large P).

    python examples/ring_attention_long_context.py --strategy zigzag
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel._compat import shard_map
from horovod_tpu.parallel.ring_attention import (reference_attention,
                                                 ring_attention)
from horovod_tpu.parallel.ulysses import ulysses_attention


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--strategy",
                        choices=["ring", "ulysses", "zigzag"],
                        default="ring")
    parser.add_argument("--seq-len", type=int, default=4096)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    args = parser.parse_args()

    hvd.init()
    n = len(jax.devices())
    mesh = make_mesh({"sp": n})
    b, t, h, d = 1, args.seq_len, args.heads, args.head_dim

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32)) * 0.1
               for _ in range(3))

    if args.strategy == "zigzag":
        from horovod_tpu.parallel import zigzag_ring_self_attention

        out = zigzag_ring_self_attention(q, k, v, mesh)
    else:
        def body(q, k, v):
            if args.strategy == "ring":
                return ring_attention(q, k, v, axis_name="sp",
                                      causal=True)
            return ulysses_attention(q, k, v, axis_name="sp",
                                     causal=True)

        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))

        out = fn(q, k, v)
    jax.block_until_ready(out)
    if hvd.rank() == 0:
        # verify against the dense oracle on a prefix
        expect = reference_attention(q[:, :256], k[:, :256], v[:, :256],
                                     causal=True)
        np.testing.assert_allclose(np.asarray(out[:, :256]),
                                   np.asarray(expect), rtol=2e-2, atol=2e-2)
        print(f"{args.strategy} attention over {n} devices: "
              f"out shape {out.shape} (verified vs dense oracle)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
