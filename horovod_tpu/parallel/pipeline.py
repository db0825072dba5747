"""Pipeline parallelism: GPipe-style microbatch pipeline over ``ppermute``.

Absent from the reference (data-parallel only, SURVEY §2.7); designed
TPU-first: every pipeline stage is one shard of a ``shard_map`` over the
``pp`` mesh axis, stage weights live sharded on that axis (stage i's
weights are shard i of a leading stage dimension), and activations hop to
the next stage with ``lax.ppermute`` — one ICI neighbor-transfer per tick,
which XLA overlaps with the next microbatch's compute.  The schedule is a
single ``lax.scan`` of ``M + S - 1`` ticks (M microbatches, S stages):
static shapes, no data-dependent control flow, fully jittable.

The stage function must be shape-preserving (``[mb, ...] -> [mb, ...]``),
which transformer blocks are.  Embedding / head layers run outside the
pipelined middle.
"""

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel._compat import shard_map


def pipeline_apply(stage_fn, stage_params, microbatches, *, axis_name="pp"):
    """Run inside ``shard_map``: push M microbatches through S stages.

    stage_fn: ``(params_for_this_stage, x) -> y`` with y.shape == x.shape.
    stage_params: this shard's slice of the stacked stage weights (pytree
        whose arrays have the stage dim already stripped by sharding, i.e.
        leading dim 1) — a leading axis of size 1 is squeezed.
    microbatches: ``[M, mb, ...]`` — replicated across the axis (every
        stage sees the full set; only stage 0 reads from it).

    Returns ``[M, mb, ...]`` outputs, valid on every shard (the last
    stage's results are broadcast back with a masked psum).
    """
    s = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + s - 1

    params = jax.tree_util.tree_map(
        lambda a: a[0] if a.ndim and a.shape[0] == 1 else a, stage_params)

    perm = [(i, (i + 1) % s) for i in range(s)]
    state0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    state0 = lax.pcast(state0, (axis_name,), to="varying")
    out0 = lax.pcast(out0, (axis_name,), to="varying")

    def tick(carry, t):
        state, outs = carry
        # stage 0 ingests microbatch t (zeros once the feed is exhausted)
        feed = microbatches[jnp.minimum(t, m - 1)]
        state = jnp.where(jnp.logical_and(idx == 0, t < m), feed, state)
        y = stage_fn(params, state)
        # the last stage retires microbatch t - (s-1) at tick t
        done = t - (s - 1)
        is_last = idx == s - 1
        outs = lax.cond(
            jnp.logical_and(is_last, done >= 0),
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(done, 0), axis=0),
            lambda o: o, outs)
        state = lax.ppermute(y, axis_name, perm)
        return (state, outs), None

    (_, outs), _ = lax.scan(tick, (state0, out0), jnp.arange(ticks))
    # replicate results from the last stage to all shards
    mask = jnp.where(idx == s - 1, 1.0, 0.0).astype(outs.dtype)
    return lax.psum(outs * mask, axis_name)


def pipelined(stage_fn, mesh, *, axis_name="pp", stage_param_specs=None,
              data_spec=None):
    """Wrap ``stage_fn`` into a global-array pipeline callable.

    Returns ``fn(stacked_params, microbatches)`` where ``stacked_params``
    arrays have a leading stage dimension of size = axis size, and
    ``microbatches`` is ``[M, mb, ...]``.

    ``mesh`` may be a ``jax`` Mesh or an ``hvd.grid(...)`` Grid
    (docs/groups.md): the grid resolves to the device mesh with the
    same C-order layout, so the ``pp`` stage sequence matches the
    grid's ``pp`` process groups.
    """
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.mesh import as_mesh

    mesh = as_mesh(mesh)

    if stage_param_specs is None:
        stage_param_specs = P(axis_name)
    if data_spec is None:
        data_spec = P()

    def run(stacked_params, microbatches):
        specs_params = jax.tree_util.tree_map(
            lambda _: stage_param_specs, stacked_params)
        fn = shard_map(
            lambda p, x: pipeline_apply(stage_fn, p, x,
                                        axis_name=axis_name),
            mesh=mesh,
            in_specs=(specs_params, data_spec),
            out_specs=data_spec,
        )
        return fn(stacked_params, microbatches)

    return run
