"""Loop ``spmd``: the training step of a user of the SPMD API.

Per-rank loss and ``jax.value_and_grad``, the gradient exchange inside
``hvd.DistributedOptimizer(...).update``, all in one jitted program over
the framework's ``shard_map`` on an ``hvd`` mesh of the cell's chips,
state donated.  Compiled once, ahead of time.  On one chip the exchange
is a no-op; on several the compiled step must hold an all-reduce and
the replicas must stay bit-identical.
"""

import types


def make_step(cell, optimizer, mesh):
    """The jitted step over ``mesh``: ``(params, extra, opt_state,
    batch) -> (params, extra, opt_state, loss)``, state donated."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel._compat import shard_map

    family = cell.family
    opt = hvd.DistributedOptimizer(optimizer)

    def per_shard(params, extra, opt_state, batch):
        (loss, extra), grads = jax.value_and_grad(
            lambda p: family.loss(cell.config, p, extra, batch),
            has_aux=True)(params)
        # non-trained state (batch statistics) is averaged over ranks
        extra = jax.tree.map(lambda s: jax.lax.pmean(s, "hvd"), extra)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), extra, opt_state,
                jax.lax.pmean(loss, "hvd"))

    step = jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(), P(), P("hvd")),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))
    return opt, step


def build(run):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel._compat import shard_map

    mesh = make_mesh({"hvd": len(run.devices)}, devices=run.devices)
    replicated = NamedSharding(mesh, P())
    opt, step = make_step(run.cell, run.optimizer(), mesh)

    with run.phase("init"):
        inputs = run.inputs(replicated, NamedSharding(mesh, P("hvd")))
        # placed as the step returns it, or the second call recompiles
        init_opt = jax.jit(opt.init, out_shardings=replicated)

        def init_state():
            params, extra = inputs.init()
            return params, extra, init_opt(params)

        state = init_state()
    with run.phase("trace_lower"):
        lowered = step.lower(*state, inputs.pool[0])
    with run.phase("compile"):
        compiled = run.programs["step"] = lowered.compile()
    del state
    text = compiled.as_text()
    run.notes["tpu_custom_call"] = text.count("tpu_custom_call")
    run.notes["all_reduces"] = (text.count(" all-reduce(")
                                + text.count(" all-reduce-start("))

    def run_step(state, batch):
        with run.span("dispatch"):
            *state, loss = compiled(*state, batch)
        return tuple(state), loss

    def check():
        if len(run.devices) > 1 and not run.notes["all_reduces"]:
            return ["the compiled step of a multi-chip cell holds no "
                    "all-reduce"]
        return []

    def check_after(state):
        """Every chip reads its OWN replica: a spread of 0 means the
        copies of each parameter are bit-identical after the window."""
        if len(run.devices) == 1:
            return []
        spread = jax.jit(shard_map(
            lambda p: jax.tree.map(
                lambda w: jax.numpy.max(jax.lax.pmax(w, "hvd")
                                        - jax.lax.pmin(w, "hvd")), p),
            mesh=mesh, in_specs=P(), out_specs=P()))(state[0])
        worst = max(float(s) for s in jax.tree.leaves(spread))
        run.notes["replica_spread"] = worst
        return [] if worst == 0.0 else [
            f"parameters differ across chips by up to {worst}"]

    return types.SimpleNamespace(
        inputs=inputs, init_state=init_state, step=run_step,
        check=check,
        check_after=check_after, close=lambda: None)
