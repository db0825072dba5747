"""Loop ``spmd_hostfed``: the ``spmd`` loop's step, fed from the host.

The step, the optimizer, the weights and the pool are ``loops/spmd.py``'s
to the digit, and so is the reference check: until ``check()`` has run
(``run.py`` calls it straight after the reference check) ``step`` feeds
the batch it is handed.  From then on the pool lives in HOST memory as
numpy arrays, the device's copy is dropped, and ``step`` takes no notice
of the batch ``run.py`` hands it: it takes the next one from the
program's own input path,

    BatchIterator(pool, batch_size=<global batch>, shuffle=True,
                  seed=<--seed>, epochs=None)
    prefetch_to_device(source, sharding=<the batch's>)   # size 2

under the host span ``input_wait``, and dispatches under ``dispatch``
without blocking.  What the path then records about itself
(``horovod_tpu/utils/trace.py``: the ``hvd.data.*`` spans, the batch
log) is read by ``input_trace.py`` and the ``input_*`` metrics.
"""

import copy


def build(run):
    import jax
    import numpy as np

    from horovod_tpu.utils.data import BatchIterator, prefetch_to_device

    loop = run.reader("loops", "spmd").build(run)
    inputs, resident = loop.inputs, copy.copy(loop)
    sharding = jax.tree.leaves(inputs.pool[0])[0].sharding

    def on_host(*leaves):
        # one array a leaf, the pool's batches end to end: a data set
        # of pool_batches x samples_per_step rows.  C-ordered, as a
        # file read from disk is: device_get hands back the DEVICE's
        # layout in strides, and concatenate would keep it
        return np.concatenate([np.ascontiguousarray(leaf)
                               for leaf in jax.device_get(leaves)])

    with run.phase("host_pool"):
        host_pool = jax.tree.map(on_host, *inputs.pool)
    fed = []  # the prefetcher, once check() has started it

    def step(state, batch):
        if fed:
            with run.span("input_wait"):
                batch = next(fed[0])
        return resident.step(state, batch)

    def check():
        failures = resident.check()
        # run.py draws from the pool by index to the end; nothing is there
        inputs.pool[:] = [None] * len(inputs.pool)
        fed.append(prefetch_to_device(
            iter(BatchIterator(host_pool, inputs.samples_per_step,
                               shuffle=True, seed=run.seed, epochs=None)),
            sharding=sharding))
        return failures

    def close():
        for prefetcher in fed:
            prefetcher.close()
        resident.close()

    loop.step, loop.check, loop.close = step, check, close
    return loop
