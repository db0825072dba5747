"""Loop ``eager``: the training step of a torch / TF / eager-JAX user
of the negotiated plane, on one rank.

A jitted forward + backward, then every gradient leaf handed to
``hvd.allreduce_async(..., op=hvd.Average, name=<leaf path>)`` in
backward order as ``horovod_tpu/torch/optimizer.py`` does,
``hvd.synchronize`` on all, then a jitted optimizer apply.  Arrays stay
on the device.  Names repeat every step, so the response cache works as
it would for a user.  With one rank the collective is an identity: what
is timed is enqueue -> negotiate -> fuse -> launch -> complete.
"""

import types


def build(run):
    import jax
    import optax
    from jax.sharding import SingleDeviceSharding

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    cell, family = run.cell, run.cell.family
    if len(run.devices) != 1:
        raise ValueError("loop 'eager' drives one rank from the main "
                         "thread; thread-ranks are a loop of their own")
    device = run.devices[0]
    with run.phase("hvd_init"):
        # builds the native core on a checkout's first run
        hvd.init(list(run.devices))
    state_now = basics._get_state()
    served_by = type(state_now.controller).__name__
    run.notes["controller"] = {"configured": state_now.config.controller,
                               "served_by": served_by}
    if (state_now.config.controller == "native"
            and served_by != "NativeController"):
        hvd.shutdown()
        raise RuntimeError(
            f"the native controller is configured but {served_by} "
            f"serves: the native core did not load")

    placed = SingleDeviceSharding(device)
    opt = run.optimizer()

    def forward_backward(params, extra, batch):
        (loss, extra), grads = jax.value_and_grad(
            lambda p: family.loss(cell.config, p, extra, batch),
            has_aux=True)(params)
        return loss, extra, grads

    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def exchange(grads):
        """Every leaf through the negotiated plane, last layer first."""
        leaves, treedef = jax.tree.flatten(grads)
        order = range(len(leaves) - 1, -1, -1)
        with run.span("enqueue"):
            handles = [hvd.allreduce_async(leaves[i], op=hvd.Average,
                                           name=names[i]) for i in order]
        with run.span("synchronize"):
            reduced = [hvd.synchronize(h) for h in handles]
        return treedef.unflatten(reduced[::-1])

    with run.phase("init"):
        inputs = run.inputs(placed, placed)
        init_opt = jax.jit(opt.init, out_shardings=placed)

        def init_state():
            params, extra = inputs.init()
            return params, extra, init_opt(params)

        params, extra, opt_state = init_state()
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
    with run.phase("trace_lower"):
        lowered = jax.jit(forward_backward).lower(
            params, extra, inputs.pool[0])
    with run.phase("compile"):
        fwd_bwd = run.programs["forward_backward"] = lowered.compile()
        # compiled for the gradients as the plane hands them back
        reduced = exchange(fwd_bwd(params, extra, inputs.pool[0])[2])
        apply_c = run.programs["apply"] = jax.jit(
            apply, donate_argnums=(0, 1)).lower(
            params, opt_state, reduced).compile()
    del params, extra, opt_state, reduced
    run.notes["gradient_tensors"] = len(names)

    def run_step(state, batch):
        params, extra, opt_state = state
        with run.span("forward_backward"):
            loss, extra, grads = fwd_bwd(params, extra, batch)
        reduced = exchange(grads)
        with run.span("apply"):
            params, opt_state = apply_c(params, opt_state, reduced)
        return (params, extra, opt_state), loss

    def check():
        """One rank, ``Average``: every reduced gradient is bit for bit
        what was handed in, and stays on its chip."""
        params, extra, _ = init_state()
        failures = []
        same = jax.jit(lambda a, b: jax.numpy.all(jax.numpy.stack([
            jax.numpy.array_equal(x, y) for x, y in zip(
                jax.tree.leaves(a), jax.tree.leaves(b))])))
        for batch in inputs.pool[:2]:
            grads = fwd_bwd(params, extra, batch)[2]
            reduced = exchange(grads)
            if not bool(same(grads, reduced)):
                failures.append("a reduced gradient differs from what "
                                "one rank handed in")
            off = [r.devices() for r in jax.tree.leaves(reduced)
                   if r.devices() != {device}]
            if off:
                failures.append(f"{len(off)} reduced gradients left the "
                                f"chip: {off[0]}")
        return failures

    return types.SimpleNamespace(
        inputs=inputs, init_state=init_state, step=run_step,
        check=check,
        check_after=lambda state: [], close=hvd.shutdown)
