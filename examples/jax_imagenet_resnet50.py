"""ImageNet ResNet-50 training — the upstream's flagship model (reference:
``examples/pytorch_imagenet_resnet50.py``): real-data pipeline with
rank-sharded loading, bf16 SPMD training step over the ``hvd`` mesh,
linear-scaled LR with warmup + staircase decay, top-1/top-5 validation
accuracy averaged across ranks, and rank-0 checkpoint/resume.

Data layout: ``--train-dir`` / ``--val-dir`` containing ``.npz`` shards
with arrays ``x`` ([N, 224, 224, 3] float32 or uint8) and ``y`` ([N]
int).  Absent dirs fall back to synthetic data so the example runs
air-gapped (same spirit as the reference's ``--synthetic`` benchmarks).

    python examples/jax_imagenet_resnet50.py --train-dir /data/train \
        --val-dir /data/val --epochs 90
    python examples/jax_imagenet_resnet50.py --epochs 1 --steps 20   # synthetic
"""

import argparse
import glob
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import callbacks
from horovod_tpu.models import ResNet50
from horovod_tpu.parallel._compat import shard_map
from horovod_tpu.utils import checkpoint as ckpt
from horovod_tpu.utils.data import prefetch_to_device


def iter_shards(data_dir, batch, rank, size, synthetic_steps, seed=0):
    """Yield (x, y) global batches; rank-sharded file reading
    (reference: DistributedSampler partitioning)."""
    files = sorted(glob.glob(os.path.join(data_dir, "*.npz"))) \
        if data_dir else []
    if not files:
        rng = np.random.RandomState(seed)
        for _ in range(synthetic_steps):
            yield (rng.rand(batch, 224, 224, 3).astype(np.float32),
                   rng.randint(0, 1000, (batch,)))
        return
    for fi, path in enumerate(files):
        if fi % size != rank and size > 1:
            continue  # each process reads its own shard files
        data = np.load(path)
        x, y = data["x"], data["y"]
        if x.dtype == np.uint8:
            x = x.astype(np.float32) / 255.0
        for i in range(0, len(x) - batch + 1, batch):
            yield x[i:i + batch], y[i:i + batch]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--train-dir", default=None)
    parser.add_argument("--val-dir", default=None)
    parser.add_argument("--epochs", type=int, default=90)
    parser.add_argument("--steps", type=int, default=50,
                        help="synthetic steps per epoch when no data dir")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-device batch size")
    parser.add_argument("--base-lr", type=float, default=0.0125,
                        help="single-device LR (scaled by world size)")
    parser.add_argument("--warmup-epochs", type=int, default=5)
    parser.add_argument("--checkpoint-dir", default=None)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.devices.size
    global_batch = args.batch_size * n

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = jax.jit(lambda r, x: model.init(r, x, train=True))(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    params, batch_stats = variables["params"], variables["batch_stats"]

    # reference LR recipe: warmup to base_lr*N over warmup epochs, then
    # staircase /10 at epochs 30/60/80
    steps_per_epoch = args.steps
    schedule = callbacks.warmup_then_piecewise(
        args.base_lr, args.warmup_epochs * steps_per_epoch,
        {30 * steps_per_epoch: 0.1, 60 * steps_per_epoch: 0.1,
         80 * steps_per_epoch: 0.1})
    opt = hvd.DistributedOptimizer(
        optax.sgd(schedule, momentum=0.9, nesterov=True),
        named_axes=("hvd",))
    opt_state = opt.init(params)

    start_epoch = 0
    if args.checkpoint_dir:
        try:
            (params, batch_stats, opt_state), start_epoch = \
                ckpt.restore_checkpoint(args.checkpoint_dir,
                                        (params, batch_stats, opt_state))
            if hvd.rank() == 0:
                print(f"resumed from epoch {start_epoch}")
        except FileNotFoundError:
            pass

    def per_shard_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(y, 1000)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * one_hot, axis=-1))
            return loss, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_stats = jax.tree.map(lambda s: jax.lax.pmean(s, "hvd"),
                                 new_stats)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats,
                opt_state, jax.lax.pmean(loss, "hvd"))

    step = jax.jit(shard_map(
        per_shard_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))

    def eval_step(params, batch_stats, x, y):
        logits = model.apply({"params": params,
                              "batch_stats": batch_stats}, x, train=False)
        top1 = jnp.mean((jnp.argmax(logits, -1) == y))
        top5 = jnp.mean(jnp.any(
            jax.lax.top_k(logits, 5)[1] == y[:, None], axis=-1))
        return top1, top5

    eval_jit = jax.jit(eval_step)

    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        images = 0
        loss = None
        # double-buffered device staging: batch N+1's host->device copy
        # overlaps step N's compute instead of serializing after it.
        # mesh= builds the GLOBAL batch from each process's local rows
        # (multi-host correct; single-process: local rows == global)
        local_batch = global_batch // jax.process_count()
        for batch in prefetch_to_device(
                iter_shards(args.train_dir, local_batch,
                            hvd.cross_rank(), hvd.cross_size(),
                            args.steps, seed=epoch),
                size=2, mesh=mesh):
            xd, yd = batch
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, xd, yd)
            images += xd.shape[0]
        loss_val = float(np.asarray(jax.device_get(loss))) \
            if loss is not None else float("nan")
        rate = images / (time.perf_counter() - t0)

        # validation (averaged across ranks like MetricAverageCallback)
        top1s, top5s = [], []
        for x, y in iter_shards(args.val_dir, global_batch, hvd.cross_rank(),
                                hvd.cross_size(), 2, seed=10_000 + epoch):
            t1, t5 = eval_jit(params, batch_stats, jnp.asarray(x),
                              jnp.asarray(y))
            top1s.append(float(t1))
            top5s.append(float(t5))
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss {loss_val:.3f} "
                  f"{rate:.1f} img/s  top1 {np.mean(top1s):.4f} "
                  f"top5 {np.mean(top5s):.4f}")
        if args.checkpoint_dir and hvd.rank() == 0:
            ckpt.save_checkpoint(args.checkpoint_dir,
                                 (params, batch_stats, opt_state),
                                 step=epoch + 1, rank=0)
    if hvd.rank() == 0:
        print("IMAGENET_RESNET50_DONE")
    hvd.shutdown()


if __name__ == "__main__":
    main()
