"""The framework's ``shard_map``: ``jax.shard_map`` without varying-axes
checking.

Every SPMD step in this repository (``DistributedOptimizer``, ZeRO, the
sequence/pipeline-parallel wrappers, the Pallas kernels) is written in
the per-rank style of the reference: each rank differentiates ITS loss
with ``jax.grad`` and the framework reduces the local gradients.  A
checked ``jax.shard_map`` changes that contract — autodiff already sums
the gradient of a replicated (``P()``) parameter over the mesh axis, so
a following ``pmean`` is the identity and the optimizer would apply N x
the mean — and its type rule rejects the Pallas ``custom_vjp`` backward
rules (a per-rank ``gamma`` cotangent for an unvarying ``gamma``).
``allreduce_gradients`` refuses gradients that arrive already reduced,
so a step wrapped in a checked ``jax.shard_map`` fails at trace time
rather than training with a wrong scale.
"""

import functools

import jax

shard_map = functools.partial(jax.shard_map, check_vma=False)
