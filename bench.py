"""Headline benchmark: ResNet-50 synthetic training throughput per chip.

Mirrors the reference's measurement vehicle
(``examples/pytorch_synthetic_benchmark.py:107-120``: img/sec mean over
timed iterations of a synthetic-data training loop).  Baseline for
``vs_baseline`` is the reference's published per-GPU throughput:
1656.82 images/sec on 16 Pascal GPUs => 103.55 img/sec/GPU
(``docs/benchmarks.rst:31-43``, BASELINE.md).

Also reports (in the same JSON object, under ``extra``):
  - ``mfu``: model-FLOPs utilization = achieved training FLOPs/s per
    chip over the chip's peak bf16 FLOPs/s (XLA cost analysis where
    available, analytic ResNet-50 estimate otherwise).
  - ``allreduce_gbs``: eager-path ``hvd.allreduce`` algorithmic
    bandwidth (GB/s) swept over payload sizes 1KB..256MB — the
    framework-overhead oracle that autotune tunes against (reference:
    ``docs/benchmarks.rst:31-43``).  Two legs: the legacy numpy
    round-trip (host -> device -> psum -> device -> host each call) and
    ``allreduce_gbs_device``, the device-resident path (jax.Array in /
    jax.Array out, one host sync at the end) — the honest measure of
    the eager plane once data lives on device.
  - ``allreduce_gbs_ring`` / ``allreduce_gbs_int8``: exact vs
    block-scaled int8 loopback-TCP worker ring.
  - ``allreduce_gbs_ring_pipelined``: the pipelined ring transfer
    engine (native wire dtypes + segment overlap + socket striping)
    swept over segment size and stripe count at 1/4/16/64 MB against
    the seed-era serial f64-wire ring (docs/benchmarks.md).
  - ``groups`` (``python bench.py --groups`` standalone): process-group
    overlap — two disjoint groups' allreduces serialized vs
    concurrently in flight on both the TCP ring plane and the public
    ``group=`` API, plus the DP x TP grid-vs-mesh transformer step
    cell (docs/groups.md).

Structure: ``python bench.py`` runs the measurement once in a fresh
subprocess (``--worker``) and exits with its code; the parent never
touches JAX, so the worker is the one process that holds the chip.
There is no retry and no CPU path: a leg that fails, or a device the
peak table does not know, fails the run.  Prints ONE JSON line at the
end.
"""

import json
import os
import socket
import subprocess
import sys
import time

BASELINE_IMG_SEC_PER_DEVICE = 1656.82 / 16.0

# Peak bf16 matmul FLOPs/s by TPU generation (public spec sheets).
_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,  # v6e
    "v6e": 918e12,
}

# Analytic fallback: ResNet-50 fwd ~4.09 GFLOPs/image @224x224; training
# (fwd + bwd) ~3x fwd.
_RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9


def _peak_flops_per_chip(device):
    kind = device.device_kind.lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}): MFU is undefined here and this "
        f"benchmark has no CPU path")


def _bench_resnet(devices, per_device_batch=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map
    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50
    from horovod_tpu.parallel import make_mesh

    n = len(devices)
    mesh = make_mesh({"hvd": n}, devices=devices)

    if per_device_batch is None:
        per_device_batch = int(os.environ.get("BENCH_BATCH", 64))
    batch = per_device_batch * n
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    x_host = np.random.RandomState(0).randn(
        batch, 224, 224, 3).astype(np.float32)
    y_host = np.random.RandomState(1).randint(0, 1000, (batch,))

    variables = jax.jit(lambda r, x: model.init(r, x, train=True))(
        rng, jnp.zeros((1, 224, 224, 3), jnp.float32))
    params, batch_stats = variables["params"], variables["batch_stats"]

    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   named_axes=("hvd",))
    opt_state = opt.init(params)

    def per_shard_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(y, 1000)
            loss = -jnp.mean(
                jnp.sum(jax.nn.log_softmax(logits) * one_hot, axis=-1))
            return loss, updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_stats = jax.tree.map(
            lambda s: jax.lax.pmean(s, "hvd"), new_stats)
        updates, new_opt_state = opt.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_stats, new_opt_state, \
            jax.lax.pmean(loss, "hvd")

    step = jax.jit(shard_map(
        per_shard_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P()),
    ), donate_argnums=(0, 1, 2))

    sharded = NamedSharding(mesh, P("hvd"))
    x = jax.device_put(x_host, sharded)
    y = jax.device_put(y_host, sharded)

    # XLA's own FLOP count for the compiled step, if the backend
    # exposes it; analytic estimate otherwise.
    cost = step.lower(params, batch_stats, opt_state, x, y) \
        .compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops_per_step = float(cost.get("flops", 0.0)) or None
    cost_info = {k: float(v) for k, v in cost.items()
                 if k in ("flops", "bytes accessed",
                          "optimal_seconds", "transcendentals")}
    if not flops_per_step:
        flops_per_step = _RESNET50_TRAIN_FLOPS_PER_IMG * batch
    _bench_resnet.last_cost_analysis = cost_info

    # device_get of the loss is the synchronization point: it cannot
    # complete before the step's program has finished on-device.
    for _ in range(int(os.environ.get("BENCH_WARMUP", 3))):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y)
    float(jax.device_get(loss))

    iters = int(os.environ.get("BENCH_ITERS", 20))
    start = time.perf_counter()
    for _ in range(iters):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y)
    float(jax.device_get(loss))
    elapsed = time.perf_counter() - start

    img_sec = batch * iters / elapsed
    img_sec_per_device = img_sec / n

    achieved = flops_per_step * iters / elapsed / n
    return img_sec_per_device, achieved / _peak_flops_per_chip(devices[0])


def _bench_transformer(devices):
    """Transformer-LM headline: tokens/sec/chip + MFU for a fixed small
    LM (bf16, seq 2048) — the vehicle that exercises all three Pallas
    kernels (flash attention, fused LayerNorm, fused softmax-xent).
    Reference vehicle: ``examples/tensorflow2_synthetic_benchmark.py``
    (same timed-synthetic-loop methodology, LM config instead of
    ResNet).  Same ``device_get`` synchronization discipline as the
    ResNet bench."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map
    import horovod_tpu as hvd
    from horovod_tpu.models import Transformer, TransformerConfig, lm_loss
    from horovod_tpu.parallel import make_mesh

    n = len(devices)
    mesh = make_mesh({"hvd": n}, devices=devices)

    seq_len = int(os.environ.get("BENCH_LM_SEQ", 2048))
    per_device_batch = int(os.environ.get("BENCH_LM_BATCH", 8))
    d_model = int(os.environ.get("BENCH_LM_DMODEL", 1024))
    n_layers = int(os.environ.get("BENCH_LM_LAYERS", 8))
    vocab = int(os.environ.get("BENCH_LM_VOCAB", 32768))
    batch = per_device_batch * n

    cfg = TransformerConfig(
        vocab_size=vocab, n_layers=n_layers, d_model=d_model,
        n_heads=d_model // 128, d_ff=4 * d_model, max_len=seq_len,
        dtype=jnp.bfloat16,
        # BENCH_LM_REMAT=1 + a bigger BENCH_LM_BATCH: the MFU lever when
        # activations bound the per-chip batch
        remat=bool(int(os.environ.get("BENCH_LM_REMAT", "0"))))
    model = Transformer(cfg)
    tokens = np.random.RandomState(0).randint(
        0, vocab, (batch, seq_len))

    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, seq_len), jnp.int32))
    params = params["params"]
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4), named_axes=("hvd",))
    opt_state = opt.init(params)

    def per_shard(params, opt_state, tokens):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            jax.lax.pmean(loss, "hvd")

    step = jax.jit(shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))

    td = jax.device_put(tokens, NamedSharding(mesh, P("hvd")))

    cost = step.lower(params, opt_state, td).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops_per_step = float(cost.get("flops", 0.0)) or None
    if not flops_per_step:
        # analytic: 6 * params * tokens per train step
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(params))
        flops_per_step = 6.0 * n_params * batch * seq_len

    for _ in range(int(os.environ.get("BENCH_WARMUP", 3))):
        params, opt_state, loss = step(params, opt_state, td)
    float(jax.device_get(loss))

    iters = int(os.environ.get("BENCH_LM_ITERS", 10))
    start = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, td)
    float(jax.device_get(loss))
    elapsed = time.perf_counter() - start

    tokens_sec_per_device = batch * seq_len * iters / elapsed / n
    mfu = flops_per_step * iters / elapsed / n \
        / _peak_flops_per_chip(devices[0])
    return {
        "tokens_sec_per_chip": round(tokens_sec_per_device, 1),
        "mfu": round(mfu, 4),
        "config": {"d_model": d_model, "n_layers": n_layers,
                   "seq_len": seq_len, "vocab": vocab,
                   "batch_per_chip": per_device_batch, "dtype": "bf16"},
    }


def _bench_allreduce_bandwidth():
    """Eager hvd.allreduce algorithmic bandwidth over a size sweep."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    sizes = [1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 24, 1 << 26,
             1 << 28]  # 1KB .. 256MB

    def sweep(rank=0):
        out = {}
        out_device = {}
        out_latency = {}
        for nbytes in sizes:
            n_elem = nbytes // 4
            x = np.ones((n_elem,), np.float32)
            # warmup; np.asarray forces the full eager round trip.
            warm = hvd.allreduce(x, name=f"bw_{nbytes}")
            np.asarray(warm)
            label = (f"{nbytes // (1 << 20)}MB" if nbytes >= (1 << 20)
                     else f"{nbytes // (1 << 10)}KB")
            if nbytes <= (1 << 16):
                # Resolution fix: at 1KB a fixed 10 iterations lands
                # under the 3-decimal rounding floor and reports 0.000
                # GB/s.  Calibrate the repeat count to a >=50ms timing
                # window, take the median of 5 windows, and report the
                # per-op latency in us alongside — the number that
                # actually characterizes this regime.
                t0 = time.perf_counter()
                np.asarray(hvd.allreduce(x, name=f"bw_{nbytes}"))
                once = time.perf_counter() - t0
                iters = min(2000, max(20, int(0.05 / max(once, 1e-7))))
                windows = []
                for _ in range(5):
                    start = time.perf_counter()
                    for _ in range(iters):
                        np.asarray(hvd.allreduce(x, name=f"bw_{nbytes}"))
                    windows.append(time.perf_counter() - start)
                elapsed = sorted(windows)[len(windows) // 2]
                out[label] = round(nbytes * iters / elapsed / 1e9, 4)
                out_latency[label] = round(elapsed / iters * 1e6, 1)
            else:
                iters = 10 if nbytes <= (1 << 22) else 3
                start = time.perf_counter()
                for _ in range(iters):
                    np.asarray(hvd.allreduce(x, name=f"bw_{nbytes}"))
                elapsed = time.perf_counter() - start
                out[label] = round(nbytes * iters / elapsed / 1e9, 3)

            # device-resident leg: the input is the warmup's on-device
            # result (jax.Array in -> jax.Array out, zero host copies);
            # Average keeps the chained values stable.  ONE host sync at
            # the end — the chain's data dependency means the final
            # np.asarray cannot complete before every step's device work
            # does.
            y = warm
            start = time.perf_counter()
            for i in range(iters):
                y = hvd.allreduce(y, name=f"bwdev_{nbytes}",
                                  op=hvd.Average)
            # 4-byte sync: the chain's data dependency forces every
            # step to finish, without charging a full D2H transfer to
            # the "zero host copies" leg
            float(y[0])
            elapsed = time.perf_counter() - start
            # 4 decimals: the calibrated small cells live well below
            # the 3-decimal floor that produced the 0.000 readings
            out_device[label] = round(nbytes * iters / elapsed / 1e9, 4)
        return out, out_device, out_latency

    if hvd.local_size() > 1:
        # multi-device (e.g. the CPU fallback): every logical rank needs
        # its own thread context; rank 0's timings are reported
        return basics.run_parallel(sweep)[0]
    return sweep()


def _bench_ring_allreduce_bandwidth(p=4):
    """Quantized TCP-ring sweep (ISSUE 1 acceptance: on payloads >= 4MB
    the int8 ring must move >= 2x the effective GB/s of the
    uncompressed ring on the same host — bytes-on-wire shrink ~4x, 2x
    end-to-end leaves room for quantize overhead).

    Same-host worker ring over real loopback TCP: ``p`` threads, one
    PeerService mailbox + RingPlane per rank, exactly the transport the
    multi-process tcp mode uses.  Effective GB/s = payload bytes x iters
    / wall time (algorithmic bandwidth, same convention as the eager
    sweep)."""
    import threading

    import numpy as np

    from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
    from horovod_tpu.run.service import network

    key = b"0" * 32
    services = [PeerService(key) for _ in range(p)]

    def resolver(rank):
        return network.MuxClient([("127.0.0.1", services[rank].port)],
                                 key, timeout=60)

    planes = [RingPlane(r, services[r], resolver) for r in range(p)]
    ring_seq = [0]

    def run_all(data, compression):
        errs = []

        def run(r):
            try:
                planes[r].allreduce(
                    ring_seq[0], data[r], list(range(p)),
                    op_average=False, world_size=p, timeout=300,
                    compression=compression)
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(p)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]

    sizes = [1 << 20, 1 << 22, 1 << 24]
    out = {"ring": {}, "int8": {}, "speedup": {}}
    try:
        for nbytes in sizes:
            n_elem = nbytes // 4
            rng = np.random.RandomState(0)
            data = [rng.randn(n_elem).astype(np.float32)
                    for _ in range(p)]
            label = (f"{nbytes // (1 << 20)}MB" if nbytes >= (1 << 20)
                     else f"{nbytes // (1 << 10)}KB")
            for comp, bucket in (("none", "ring"), ("int8", "int8")):
                ring_seq[0] += 1
                run_all(data, comp)  # warmup (connection setup)
                iters = 3
                start = time.perf_counter()
                for _ in range(iters):
                    ring_seq[0] += 1
                    run_all(data, comp)
                elapsed = time.perf_counter() - start
                out[bucket][label] = round(
                    nbytes * iters / elapsed / 1e9, 3)
            out["speedup"][label] = round(
                out["int8"][label] / out["ring"][label], 2)
    finally:
        for plane in planes:
            plane.close()
        for svc in services:
            svc.shutdown()
    return out


def _ring_harness(p, segment_bytes, stripes, reconnect_budget=None):
    """In-process worker ring over real loopback TCP (the exact
    transport of multi-process tcp mode): one PeerService mailbox +
    RingPlane per rank, control MuxClients + bulk StripeClients.
    ``reconnect_budget`` arms the self-healing session layer explicitly
    (None = the env default, i.e. off) — the reconnect leg passes it as
    a ctor kwarg so the measurement never mutates process env."""
    from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
    from horovod_tpu.run.service import network

    key = b"0" * 32
    services = [PeerService(key) for _ in range(p)]

    def resolver(rank):
        return network.MuxClient([("127.0.0.1", services[rank].port)],
                                 key, timeout=60,
                                 reconnect_budget=reconnect_budget)

    def resolve_bulk(rank):
        return network.StripeClient(
            [("127.0.0.1", services[rank].port)], key, timeout=60,
            reconnect_budget=reconnect_budget)

    planes = [RingPlane(r, services[r], resolver, resolve_bulk,
                        segment_bytes=segment_bytes, stripes=stripes)
              for r in range(p)]
    return services, planes


def _ring_run_all(planes, fn):
    import threading

    errs = []

    def run(r):
        try:
            fn(r)
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(len(planes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]


def _tcp_local_groups(p, local_size):
    """HVD_HIER_LOCAL_SIZE-style group plan over loopback planes:
    consecutive ``local_size`` chunks of the sorted rank list (the same
    rule the tcp coordinator's ``_plan_groups`` applies)."""
    return [list(range(lo, min(lo + local_size, p)))
            for lo in range(0, p, local_size)]


def _bench_tcp_scaling(ranks=(1, 2, 4, 8), payload_bytes=1 << 14,
                       local_size=2, compute_ms=3.0, step_iters=20,
                       step_windows=3, latency_bytes=1 << 14,
                       latency_iters=30):
    """TCP-plane schedule scaling probe (ISSUE 12): the 1/2/4/8-rank
    efficiency curve of a synthetic train step — a fixed device-compute
    stage plus one gradient-bucket allreduce over the real loopback
    transport — for the flat ring vs the two-level hierarchical
    schedule (groups = HVD_HIER_LOCAL_SIZE-style chunks of
    ``local_size``), plus a 16KB 8-rank latency cell (flat ring vs
    recursive halving/doubling, medians over timing windows).

    The compute stage is a GIL-free fixed-latency sleep, modeling
    accelerator-resident work: on a real TPU host XLA owns the chips
    and the host CPU runs only the data plane, so p ranks' compute
    phases overlap regardless of host core count (a host-side BLAS
    kernel would instead serialize on this boxes' core budget and
    measure the hardware, not the schedule).

    Efficiency = step(1) / step(p): per-rank work is constant (weak
    scaling), so everything lost below 1.0 is collective overhead, and
    the schedule with the shorter serialized-round critical path keeps
    the curve flatter — the flat ring pays 2(p-1) rounds and p·2(p-1)
    mailbox messages; the two-level plan pays (g-1) + 2(G-1) + 2
    rounds and roughly a third of the messages at p=8."""
    import numpy as np

    def run_steps(planes, p, schedule, groups, data, seq, iters,
                  compute=True):
        def fn(r):
            part = list(range(p))
            kw = dict(op_average=False, world_size=p, timeout=120)
            for i in range(iters):
                if compute:
                    time.sleep(compute_ms / 1e3)
                rid = seq[0] + i
                if schedule == "hierarchical":
                    planes[r].allreduce_hierarchical(
                        rid, data[r], part, groups, **kw)
                elif schedule == "rhd":
                    planes[r].allreduce_rhd(rid, data[r], part, **kw)
                else:
                    planes[r].allreduce(rid, data[r], part, **kw)

        start = time.perf_counter()
        _ring_run_all(planes, fn)
        elapsed = time.perf_counter() - start
        seq[0] += iters
        return elapsed / iters

    def median_steps(planes, p, schedule, groups, data, seq,
                     windows, iters, compute=True):
        run_steps(planes, p, schedule, groups, data, seq, 4,
                  compute=compute)  # warmup: connections + codepaths
        ws = [run_steps(planes, p, schedule, groups, data, seq, iters,
                        compute=compute) for _ in range(windows)]
        return sorted(ws)[len(ws) // 2]

    out = {"step_ms": {"flat_ring": {}, "hierarchical": {}},
           "efficiency": {"flat_ring": {}, "hierarchical": {}},
           "latency_us_16KB_8ranks": {},
           "payload_bytes": payload_bytes, "local_size": local_size,
           "compute_ms": compute_ms}
    base_ms = None
    for p in ranks:
        services, planes = _ring_harness(p, 1 << 20, 2)
        seq = [1]
        rng = np.random.RandomState(1)
        data = [rng.rand(payload_bytes // 4).astype(np.float32)
                for _ in range(p)]
        groups = _tcp_local_groups(p, local_size)
        try:
            flat_s = median_steps(planes, p, "flat_ring", None, data,
                                  seq, step_windows, step_iters)
            hier_s = median_steps(planes, p, "hierarchical", groups,
                                  data, seq, step_windows, step_iters)
            if base_ms is None:
                # p=1: both schedules degenerate to the same no-wire
                # reduction; flat_ring's number is the common base
                base_ms = flat_s * 1e3
            out["step_ms"]["flat_ring"][str(p)] = round(flat_s * 1e3, 3)
            out["step_ms"]["hierarchical"][str(p)] = round(
                hier_s * 1e3, 3)
            out["efficiency"]["flat_ring"][str(p)] = round(
                base_ms / (flat_s * 1e3), 3)
            out["efficiency"]["hierarchical"][str(p)] = round(
                base_ms / (hier_s * 1e3), 3)
            if p == 8:
                # latency cell: pure allreduce (no compute stage),
                # median of 3 windows of back-to-back ops
                lat = [rng.rand(latency_bytes // 4).astype(np.float32)
                       for _ in range(p)]
                for sched in ("flat_ring", "rhd"):
                    med = median_steps(planes, p, sched, None, lat,
                                       seq, 3, latency_iters,
                                       compute=False)
                    out["latency_us_16KB_8ranks"][sched] = round(
                        med * 1e6, 1)
        finally:
            for plane in planes:
                plane.close()
            for svc in services:
                svc.shutdown()
    return out


def _bench_group_overlap(p=8, group_size=4, payload_bytes=1 << 14,
                         compute_ms=20.0, iters=4, windows=3):
    """Process-group overlap probe (ISSUE 14, docs/groups.md): two
    disjoint groups' allreduces over the real loopback transport,
    serialized (group A's whole run completes before group B starts)
    vs concurrently in flight.  Each step is a GIL-free compute stage
    plus one group-ring allreduce — the model is a TP group on one
    half of the job and a DP bucket on the other half of the same
    step.  A data plane with any cross-group serialization point (a
    shared ring lock, coordinator head-of-line blocking, a global ring
    namespace) pins concurrent time to serial time; independent
    per-group planes push ``overlap_speedup`` toward 2x.

    The compute stage is a GIL-free fixed-latency sleep standing in
    for accelerator-resident work (same rationale as
    ``_bench_tcp_scaling``), and the payload is small so the step is
    compute-dominated: on a loaded/1-core CI host the host-CPU cost of
    the reduction itself cannot overlap, and making it dominant would
    measure this box's core count instead of whether the transport
    serializes the two groups."""
    import threading

    import numpy as np

    groups = [list(range(group_size)), list(range(group_size, p))]

    def run_ranks(ranks, fn):
        errs = []

        def run(r):
            try:
                fn(r)
            except Exception as exc:  # noqa: BLE001 — reraised below
                errs.append(exc)

        threads = [threading.Thread(target=run, args=(r,))
                   for r in ranks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]

    services, planes = _ring_harness(p, 1 << 20, 2)
    seq = [1]
    rng = np.random.RandomState(2)
    data = [rng.rand(payload_bytes // 4).astype(np.float32)
            for _ in range(p)]

    def steps(gi, r, base_rid):
        grp = groups[gi]
        # disjoint rid namespaces per group, as the controller's
        # group-scoped ring-id allocator guarantees on the real path
        for i in range(iters):
            time.sleep(compute_ms / 1e3)
            planes[r].allreduce(base_rid + gi * 1_000_000 + i, data[r],
                                grp, op_average=False,
                                world_size=len(grp), timeout=120)

    def serial_run():
        base = seq[0]
        seq[0] += iters
        start = time.perf_counter()
        for gi, grp in enumerate(groups):
            run_ranks(grp, lambda r, gi=gi: steps(gi, r, base))
        return time.perf_counter() - start

    def concurrent_run():
        base = seq[0]
        seq[0] += iters
        start = time.perf_counter()
        run_ranks(range(p), lambda r: steps(
            0 if r in groups[0] else 1, r, base))
        return time.perf_counter() - start

    try:
        serial_run()      # warmup: connection setup + codepaths
        concurrent_run()
        serial_s = sorted(serial_run() for _ in range(windows))[
            windows // 2]
        conc_s = sorted(concurrent_run() for _ in range(windows))[
            windows // 2]
    finally:
        for plane in planes:
            plane.close()
        for svc in services:
            svc.shutdown()
    return {"serial_ms": round(serial_s * 1e3, 3),
            "concurrent_ms": round(conc_s * 1e3, 3),
            "overlap_speedup": round(serial_s / conc_s, 3),
            "groups": [len(g) for g in groups],
            "payload_bytes": payload_bytes, "compute_ms": compute_ms,
            "iters": iters}


def _bench_ring_pipelined_bandwidth(p=4):
    """Pipelined exact-ring sweep (ISSUE 3): effective GB/s of the
    native-dtype segmented/striped ring vs the seed-era serial
    f64-on-the-wire ring, across payload sizes and (segment, stripe)
    settings.  Effective GB/s = payload bytes x iters / wall time
    (algorithmic bandwidth, same convention as the eager sweep)."""
    import numpy as np

    sizes = [1 << 20, 1 << 22, 1 << 24, 1 << 26]
    combos = [("seg256KB_s2", 1 << 18, 2), ("seg1MB_s1", 1 << 20, 1),
              ("seg1MB_s2", 1 << 20, 2), ("seg1MB_s4", 1 << 20, 4),
              ("seg4MB_s2", 1 << 22, 2)]
    services, planes = _ring_harness(p, 1 << 20, max(c[2] for c in combos))
    ring_seq = [0]

    def measure(data, run_one, iters=3):
        ring_seq[0] += 1
        _ring_run_all(planes, lambda r: run_one(r, ring_seq[0]))  # warmup
        start = time.perf_counter()
        for _ in range(iters):
            ring_seq[0] += 1
            _ring_run_all(planes, lambda r: run_one(r, ring_seq[0]))
        return data[0].nbytes * iters / (time.perf_counter() - start) / 1e9

    out = {}
    try:
        for nbytes in sizes:
            rng = np.random.RandomState(0)
            data = [rng.randn(nbytes // 4).astype(np.float32)
                    for _ in range(p)]
            label = f"{nbytes // (1 << 20)}MB"
            row = {"seed": round(measure(data, lambda r, rid:
                   planes[r].allreduce_seed(
                       rid, data[r], list(range(p)), op_average=False,
                       world_size=p, timeout=300)), 3)}
            for name, seg, stripes in combos:
                for plane in planes:
                    plane.stripes = stripes
                row[name] = round(measure(data, lambda r, rid:
                    planes[r].allreduce(
                        rid, data[r], list(range(p)), op_average=False,
                        world_size=p, timeout=300,
                        segment_bytes=seg)), 3)
            best = max(v for k, v in row.items() if k != "seed")
            row["speedup_vs_seed"] = round(best / row["seed"], 2)
            out[label] = row
    finally:
        for plane in planes:
            plane.close()
        for svc in services:
            svc.shutdown()
    return out


def _bench_reconnect(heal_trials=5, p=2, nbytes=1 << 23, iters=5,
                     windows=3):
    """Self-healing transport leg (ISSUE 17, docs/fault_tolerance.md
    "connection blips vs dead peers"): two cells, one dict, all
    in-process loopback (no fault spec — the injector is process-global
    and would cut EVERY rank's links; the bench severs one client's
    socket directly, which is exactly what an injected RST does to it).

    - ``heal_ms``: wall time for a bulk StripeClient to notice a dead
      socket mid-stream, reconnect, resume its session and replay the
      unacked window — measured as the duration of the first
      ``post_bulk`` after the socket is shut down under it.  Median
      and max over ``heal_trials`` severs.
    - ``session_on/off_gbs``: pipelined-ring allreduce GB/s with the
      session layer armed (explicit ``reconnect_budget=`` ctor kwarg)
      vs off (budget None -> legacy byte-identical wire).  The
      steady-state seq/ack overhead must stay <= 2%
      (tests/test_bench_gate.py gates the ratio)."""
    import numpy as np

    from horovod_tpu.ops.tcp_dataplane import ChunkMsg, PeerService
    from horovod_tpu.run.service import network

    key = b"0" * 32

    # --- cell 1: heal latency of a severed bulk stripe
    svc = PeerService(key)
    client = network.StripeClient([("127.0.0.1", svc.port)], key,
                                  timeout=60, reconnect_budget=30.0)
    payload = b"\x5a" * (1 << 16)
    heals_ms = []
    healed_before = network.session_stats()["reconnects_healed"]
    try:
        for i in range(4):   # establish the session + a window
            client.post_bulk(ChunkMsg((0, i), 0, None), payload)
        for t in range(heal_trials):
            with client._lock:
                sock = client._sock
            sock.shutdown(socket.SHUT_RDWR)
            t0 = time.perf_counter()
            client.post_bulk(ChunkMsg((1, t), 0, None), payload)
            heals_ms.append((time.perf_counter() - t0) * 1e3)
    finally:
        client.close()
        svc.shutdown()
    healed = network.session_stats()["reconnects_healed"] - healed_before

    # --- cell 2: steady-state session overhead on the pipelined ring
    def ring_gbs(budget):
        services, planes = _ring_harness(p, 1 << 20, 2,
                                         reconnect_budget=budget)
        rng = np.random.RandomState(0)
        data = [rng.randn(nbytes // 4).astype(np.float32)
                for _ in range(p)]
        seq = [0]

        def one():
            seq[0] += 1
            rid = seq[0]
            _ring_run_all(planes, lambda r: planes[r].allreduce(
                rid, data[r], list(range(p)), op_average=False,
                world_size=p, timeout=300, segment_bytes=1 << 20))

        try:
            one()   # warmup (connections + session handshakes)
            samples = []
            for _ in range(windows):
                start = time.perf_counter()
                for _ in range(iters):
                    one()
                samples.append(
                    nbytes * iters / (time.perf_counter() - start) / 1e9)
            return sorted(samples)[len(samples) // 2]
        finally:
            for plane in planes:
                plane.close()
            for s in services:
                s.shutdown()

    off = ring_gbs(None)
    on = ring_gbs(30.0)
    return {
        "heal_ms_median": round(sorted(heals_ms)[len(heals_ms) // 2], 3),
        "heal_ms_max": round(max(heals_ms), 3),
        "heal_trials": heal_trials,
        "reconnects_healed": healed,
        "session_off_gbs": round(off, 3),
        "session_on_gbs": round(on, 3),
        "session_overhead_pct": round((1.0 - on / off) * 100.0, 2),
        "payload_bytes": nbytes, "ranks": p,
    }


def reconnect_worker():
    """Subprocess entry for the reconnect leg: pure loopback sockets +
    threads (no JAX backend), isolated because the session-layer heal
    counters and the fault injector are process-global state."""
    print(json.dumps(_bench_reconnect()))


def _run_reconnect(timeout=600):
    """Run the self-healing transport leg in a subprocess; returns the
    dict, or None when it failed."""
    line, _, _ = _run_worker_once(flag="--reconnect-worker",
                                  extra_env={"JAX_PLATFORMS": "cpu"},
                                  timeout=timeout)
    return None if line is None else json.loads(line)


def _bench_optimizer_state_bytes():
    """Per-rank optimizer-state footprint, replicated vs ZeRO-sharded
    (docs/sharding.md): adam state bytes for a flat parameter vector at
    world sizes 1/2/4/8.  The sharded figure is the LARGEST rank's
    (np.array_split gives the first ranks one extra element) and must
    scale ~1/N — the whole point of the sharded update."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.sharding.zero import zero_shard_layout

    n_params = int(os.environ.get("BENCH_ZERO_PARAMS", 1 << 20))
    params = jnp.zeros((n_params,), jnp.float32)
    opt = optax.adam(1e-3)

    def nbytes(state):
        return int(sum(np.asarray(l).nbytes
                       for l in jax.tree.leaves(state)))

    replicated = nbytes(opt.init(params))
    out = {"n_params": n_params, "replicated_bytes": replicated,
           "zero_max_rank_bytes": {}, "zero_ratio": {}}
    for world in (1, 2, 4, 8):
        per_rank = []
        for rank in range(world):
            _, off, cnt = zero_shard_layout(n_params, world, rank)
            per_rank.append(nbytes(opt.init(params[off:off + cnt])))
        out["zero_max_rank_bytes"][str(world)] = max(per_rank)
        out["zero_ratio"][str(world)] = round(
            max(per_rank) / replicated, 4)
    return out


def _bench_sharded_step():
    """ZeRO vs replicated eager step throughput on the current topology
    (docs/sharding.md): both legs run the SAME machinery
    (ZeroDistributedOptimizer; min_size forces the replicated fallback
    for the baseline), so the ratio isolates reduce-scatter + shard
    update + allgather vs allreduce + full update."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    n_params = int(os.environ.get("BENCH_ZERO_STEP_PARAMS", 1 << 18))
    steps = int(os.environ.get("BENCH_ZERO_STEPS", 10))

    def leg(min_size):
        def run(rank=0):
            params = jnp.zeros((n_params,), jnp.float32)
            opt = hvd.ZeroDistributedOptimizer(optax.adam(1e-3),
                                               min_size=min_size)
            state = opt.init(params)
            grad = jnp.ones((n_params,), jnp.float32)
            upd, state = opt.update(grad, state, params)  # warmup
            p = optax.apply_updates(params, upd)
            float(np.asarray(p[0]))
            start = time.perf_counter()
            s = state
            for _ in range(steps):
                upd, s = opt.update(grad, s, p)
                p = optax.apply_updates(p, upd)
            float(np.asarray(p[0]))
            return time.perf_counter() - start

        if hvd.local_size() > 1:
            return basics.run_parallel(run)[0]
        return run()

    replicated_s = leg(min_size=n_params + 1)   # forces fallback
    sharded_s = leg(min_size=1)
    return {
        "n_params": n_params, "steps": steps,
        "replicated_steps_per_s": round(steps / replicated_s, 2),
        "sharded_steps_per_s": round(steps / sharded_s, 2),
        "sharded_vs_replicated": round(replicated_s / sharded_s, 3),
    }


def sharding_worker():
    """Sharding legs (docs/sharding.md), CPU-mesh by default like the
    scaling harness; runs unchanged on real chips.  Prints one JSON
    object (not the driver headline line)."""
    import jax

    if not os.environ.get("BENCH_SHARDING_REAL"):
        jax.config.update("jax_platforms", "cpu")

    import horovod_tpu as hvd

    hvd.init()
    out = {
        "optimizer_state_bytes": _bench_optimizer_state_bytes(),
        "sharded_step": _bench_sharded_step(),
        "n_ranks": hvd.size(),
        "platform": jax.devices()[0].platform,
    }
    hvd.shutdown()
    print(json.dumps(out))


def _run_sharding(timeout=600):
    """Run the sharding legs in a CPU-forced subprocess; returns the
    parsed dict or None."""
    line, _, _ = _run_worker_once(
        flag="--sharding-worker",
        extra_env={"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                                 " --xla_force_host_platform_device_count=4"
                                 ).strip()},
        timeout=timeout)
    if line is None:
        return None
    return json.loads(line)


def checkpoint_bench():
    """Durable-checkpoint overhead leg (docs/checkpoint.md): time N
    elastic commits bare vs with the background writer attached at
    interval 1 (the worst case), plus the resume (read + digest-verify
    + reassemble) latency.  Single process on the CPU mesh — the writer
    thread and the file formats are platform-independent."""
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from horovod_tpu.checkpoint import CheckpointManager
    from horovod_tpu.elastic import State

    n_params = int(os.environ.get("BENCH_CKPT_PARAMS", 1 << 20))
    steps = int(os.environ.get("BENCH_CKPT_STEPS", 20))

    def run(manager):
        params = np.zeros((n_params,), np.float32)
        opt = {"m": np.zeros((n_params,), np.float32),
               "count": np.zeros((), np.int32)}
        state = State(params=params, optimizer_state=opt)
        if manager is not None:
            state.attach_checkpoint(manager)
        start = time.perf_counter()
        for _ in range(steps):
            state.params = state.params + 1.0
            state.step += 1
            state.commit()
        elapsed = time.perf_counter() - start
        if manager is not None:
            manager.wait()
        return elapsed, state

    bare_s, _ = run(None)
    with tempfile.TemporaryDirectory() as d:
        manager = CheckpointManager(d, interval_steps=1, keep=2)
        ckpt_s, state = run(manager)
        manager.wait()
        fresh = State(params=np.zeros((n_params,), np.float32),
                      optimizer_state={"m": np.zeros((n_params,),
                                                     np.float32),
                                       "count": np.zeros((), np.int32)})
        t0 = time.perf_counter()
        resumed = manager.restore_latest(fresh)
        resume_s = time.perf_counter() - t0
        manager.close()
    out = {
        "n_params": n_params, "steps": steps,
        "commit_steps_per_s": round(steps / bare_s, 2),
        "ckpt_steps_per_s": round(steps / ckpt_s, 2),
        "ckpt_overhead": round(ckpt_s / bare_s, 3),
        "resume_s": round(resume_s, 4),
        "resumed_step": None if resumed is None else resumed[0],
    }
    print(json.dumps(out))
    return 0 if resumed is not None and fresh.step == state.step else 1


def worker():
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    # fail here, before any compile, on a device the peak table does
    # not know — the CPU included
    _peak_flops_per_chip(devices[0])

    import horovod_tpu as hvd
    hvd.init()

    img_sec_per_device, mfu = _bench_resnet(devices)
    record = {
        "metric": "resnet50_synthetic_img_sec_per_chip",
        "value": round(img_sec_per_device, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            img_sec_per_device / BASELINE_IMG_SEC_PER_DEVICE, 3),
        "extra": {
            "platform": platform,
            "n_devices": len(devices),
            "mfu": round(mfu, 4),
        },
    }
    extra = record["extra"]

    # a leg that raises fails the run: a record with a hole in it has
    # been mistaken for a measurement before
    if not os.environ.get("BENCH_SKIP_BS128"):
        # MXU occupancy leg: bs=64/chip is the reference-parity config
        # (headline); bs=128 fills the late small-spatial stages better
        v, m = _bench_resnet(devices, per_device_batch=128)
        extra["resnet_bs128"] = {"img_sec_per_chip": round(v, 2),
                                 "mfu": round(m, 4)}
    extra["transformer"] = _bench_transformer(devices)
    gbs, gbs_device, lat_us = _bench_allreduce_bandwidth()
    extra["allreduce_gbs"] = gbs
    extra["allreduce_gbs_device"] = gbs_device
    extra["allreduce_latency_us"] = lat_us
    ring = _bench_ring_allreduce_bandwidth()
    extra["allreduce_gbs_ring"] = ring["ring"]
    extra["allreduce_gbs_int8"] = ring["int8"]
    extra["allreduce_int8_speedup"] = ring["speedup"]
    extra["allreduce_gbs_ring_pipelined"] = \
        _bench_ring_pipelined_bandwidth()
    print(json.dumps(record), flush=True)
    hvd.shutdown()


def scaling_worker():
    """Scaling-efficiency harness (BASELINE.md north star: the
    reference's 8->64-GPU 90% scaling efficiency, ``docs/benchmarks.rst``).
    Runs on the virtual CPU mesh today (mesh sizes 1/2/4/8) and on real
    multi-chip unchanged when pod hardware exists: for each mesh size it
    measures the fused-SPMD allreduce bus bandwidth and a synthetic
    per-shard train step at FIXED per-device batch (weak scaling), and
    reports efficiency = step_ms(1) / step_ms(n) — 1.0 is perfect.

    Prints one JSON object (not the driver headline line)."""
    import jax

    if not os.environ.get("BENCH_SCALING_REAL"):
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map
    import horovod_tpu as hvd
    from horovod_tpu.models import MLP
    from horovod_tpu.parallel import make_mesh

    all_devices = jax.devices()
    sizes = [n for n in (1, 2, 4, 8, 16, 32, 64)
             if n <= len(all_devices)]
    per_device_batch = int(os.environ.get("BENCH_SCALING_BATCH", 8))
    ar_bytes = int(os.environ.get("BENCH_SCALING_AR_BYTES", 4 << 20))

    results = {}
    for n in sizes:
        devices = all_devices[:n]
        mesh = make_mesh({"hvd": n}, devices=devices)
        sharded = NamedSharding(mesh, P("hvd"))
        replicated = NamedSharding(mesh, P())

        # -- fused-SPMD allreduce (the DistributedOptimizer hot path):
        # one jitted psum program over the mesh
        x = jax.device_put(
            np.ones((n, ar_bytes // 4), np.float32),
            NamedSharding(mesh, P("hvd", None)))

        def ar_shard(x):
            return jax.lax.psum(x, "hvd")

        ar = jax.jit(shard_map(
            ar_shard, mesh=mesh, in_specs=P("hvd", None),
            out_specs=P("hvd", None)))
        out = ar(x)
        float(jax.device_get(out[0, 0]))  # warmup + sync
        iters = 20
        start = time.perf_counter()
        for _ in range(iters):
            out = ar(x)
        float(jax.device_get(out[0, 0]))
        elapsed = time.perf_counter() - start
        # bus bandwidth convention (NCCL tests): 2*(n-1)/n * bytes / time
        algo_gbs = ar_bytes * iters / elapsed / 1e9
        bus_gbs = algo_gbs * (2 * (n - 1) / n) if n > 1 else algo_gbs

        # -- synthetic train step, fixed per-device batch (weak scaling)
        model = MLP(features=(256, 128, 10))
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 784), jnp.float32))["params"]
        opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                       named_axes=("hvd",))
        opt_state = opt.init(params)
        xb = jax.device_put(
            np.random.RandomState(0).randn(
                per_device_batch * n, 784).astype(np.float32), sharded)
        yb = jax.device_put(
            np.random.RandomState(1).randint(
                0, 10, (per_device_batch * n,)), sharded)

        def per_shard_step(params, opt_state, xb, yb):
            def loss_fn(p):
                logits = model.apply({"params": p}, xb)
                one_hot = jax.nn.one_hot(yb, 10)
                return -jnp.mean(jnp.sum(
                    jax.nn.log_softmax(logits) * one_hot, axis=-1))

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, \
                jax.lax.pmean(loss, "hvd")

        step = jax.jit(shard_map(
            per_shard_step, mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P())), donate_argnums=(0, 1))
        params = jax.device_put(params, replicated)
        opt_state = jax.device_put(opt_state, replicated)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, xb, yb)
        float(jax.device_get(loss))
        iters = 30
        start = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, xb, yb)
        float(jax.device_get(loss))
        step_ms = (time.perf_counter() - start) / iters * 1e3

        results[str(n)] = {"allreduce_bus_gbs": round(bus_gbs, 3),
                           "step_ms": round(step_ms, 3)}

    base = results[str(sizes[0])]["step_ms"]
    for n in sizes:
        results[str(n)]["efficiency"] = round(
            base / results[str(n)]["step_ms"], 3)
    print(json.dumps({"scaling": results,
                      "platform": all_devices[0].platform,
                      "per_device_batch": per_device_batch}))


def groups_worker():
    """Process-group legs (ISSUE 14, docs/groups.md) on the virtual
    CPU mesh (real chips unchanged: unset the CPU pin).  Two cells,
    one JSON object:

    - ``api_overlap``: two disjoint groups' allreduces through the
      REAL public API (``hvd.allreduce(..., group=...)``) from
      per-rank threads, a serialized pass vs a concurrent pass, with
      the registry's own ``max_concurrent_groups`` gauge snapshotted
      after each — the serialized pass must read 1 and the concurrent
      pass >= 2, which is the "verifiably in flight at once" evidence
      (asserted, not assumed).
    - ``dp_tp_step``: transformer train-step time with params sharded
      through ``hvd.grid(dp=2, tp=4)`` vs the explicit mesh — the
      grid resolves to the same device mesh, so the ratio is a
      regression tripwire for the grid-as-mesh path."""
    import jax

    if not os.environ.get("BENCH_GROUPS_REAL"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import groups as groups_mod
    from horovod_tpu.common import basics

    devices = jax.devices()
    hvd.init()
    n = hvd.size()
    half = n // 2
    g0 = hvd.new_group(list(range(half)), name="bench.g0")
    g1 = hvd.new_group(list(range(half, n)), name="bench.g1")
    n_elem = int(os.environ.get("BENCH_GROUPS_BYTES", 1 << 14)) // 4
    iters = int(os.environ.get("BENCH_GROUPS_ITERS", 4))
    compute_ms = float(os.environ.get("BENCH_GROUPS_COMPUTE_MS", 15.0))

    def member_steps(r, grp, tag):
        x = jnp.ones((n_elem,), jnp.float32) * (r + 1)
        for i in range(iters):
            time.sleep(compute_ms / 1e3)
            hvd.allreduce(x, op=hvd.Sum, name=f"bench.{tag}.{i}",
                          group=grp)

    def serial_pass(tag):
        start = time.perf_counter()
        for grp in (g0, g1):
            basics.run_parallel(
                lambda r, grp=grp: member_steps(r, grp, tag)
                if r in grp else None)
        return time.perf_counter() - start

    def concurrent_pass(tag):
        start = time.perf_counter()
        basics.run_parallel(
            lambda r: member_steps(r, g0 if r in g0 else g1, tag))
        return time.perf_counter() - start

    serial_pass("warm.s")
    serial_s = serial_pass("timed.s")
    inflight_serial = groups_mod.stats()["max_concurrent_groups"]
    concurrent_pass("warm.c")
    conc_s = concurrent_pass("timed.c")
    inflight_conc = groups_mod.stats()["max_concurrent_groups"]
    api_overlap = {
        "serial_ms": round(serial_s * 1e3, 3),
        "concurrent_ms": round(conc_s * 1e3, 3),
        "overlap_speedup": round(serial_s / conc_s, 3),
        "max_concurrent_groups_serialized": inflight_serial,
        "max_concurrent_groups": inflight_conc,
        "iters": iters, "payload_bytes": n_elem * 4,
        "compute_ms": compute_ms,
    }

    # -- DP x TP transformer step through the grid vs the explicit mesh
    import optax

    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import make_mesh, shard_params

    cfg = TransformerConfig(
        vocab_size=int(os.environ.get("BENCH_GROUPS_VOCAB", 512)),
        n_layers=2, d_model=128, n_heads=8, d_ff=256, max_len=64,
        dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 32)))
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = optax.sgd(0.01)

    @jax.jit
    def step(p, opt_state, toks):
        def loss_fn(p):
            logits = model.apply({"params": p}, toks)
            one_hot = jax.nn.one_hot(toks, cfg.vocab_size)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * one_hot, axis=-1))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, opt_state = opt.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    def step_ms(mesh_or_grid):
        p = shard_params(params, mesh_or_grid)
        opt_state = opt.init(p)
        p, opt_state, loss = step(p, opt_state, tokens)
        float(jax.device_get(loss))  # compile + sync
        step_iters = int(os.environ.get("BENCH_GROUPS_STEP_ITERS", 6))
        start = time.perf_counter()
        for _ in range(step_iters):
            p, opt_state, loss = step(p, opt_state, tokens)
        float(jax.device_get(loss))
        return (time.perf_counter() - start) / step_iters * 1e3

    grd = hvd.grid(dp=2, tp=4)
    grid_ms = step_ms(grd)
    mesh_ms = step_ms(make_mesh({"dp": 2, "tp": 4}))
    dp_tp_step = {"grid_step_ms": round(grid_ms, 3),
                  "mesh_step_ms": round(mesh_ms, 3),
                  "grid_vs_mesh": round(grid_ms / mesh_ms, 3)}

    print(json.dumps({"api_overlap": api_overlap,
                      "dp_tp_step": dp_tp_step,
                      "platform": devices[0].platform}))
    hvd.shutdown()


def _run_groups(timeout=600):
    """Run the process-group harness in a CPU-forced subprocess, then
    attach the TCP-plane overlap probe (in-process: pure loopback
    sockets + threads, no JAX backend involved); returns the merged
    dict, or None when both legs failed."""
    line, _, _ = _run_worker_once(
        flag="--groups-worker",
        extra_env={"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                                 " --xla_force_host_platform_device_count=8"
                                 ).strip()},
        timeout=timeout)
    result = {} if line is None else json.loads(line)
    try:
        result["tcp_plane_overlap"] = _bench_group_overlap()
    except Exception as exc:  # noqa: BLE001 — keep the XLA cells
        sys.stderr.write(f"tcp-plane group overlap probe failed: "
                         f"{exc!r}\n")
    return result or None


def _bench_pipeline(devices, steps=None, batch=None, img=None):
    """Input-pipeline overlap measurement: the same host-fed training
    loop with and without ``prefetch_to_device``.  The copy cost the
    prefetcher hides is the host→device batch transfer — negligible on
    the CPU mesh (gain ≈ 1.0 expected); not measured on the current
    chip.  Returns {img_sec_plain, img_sec_prefetch, overlap_gain}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel._compat import shard_map
    from horovod_tpu.utils.data import prefetch_to_device

    n = len(devices)
    on_tpu = devices[0].platform == "tpu"
    # CPU-mesh smoke shapes vs real-chip shapes: the conv at full
    # ImageNet size is minutes per step on 8 virtual CPU devices
    steps = steps or (48 if on_tpu else 10)
    batch = batch or (32 if on_tpu else 4)
    img = img or (224 if on_tpu else 64)
    mesh = make_mesh({"hvd": n}, devices=devices)
    sharded = NamedSharding(mesh, P("hvd"))
    global_batch = batch * n

    # small conv stack: enough compute to overlap against, small enough
    # that the [B,224,224,3] host->device copy is a real fraction
    key = jax.random.PRNGKey(0)
    params = {
        "w1": jax.random.normal(key, (3, 3, 3, 16), jnp.bfloat16) * 0.1,
        "w2": jax.random.normal(key, (3, 3, 16, 16), jnp.bfloat16) * 0.1,
    }

    def per_shard(params, x):
        h = jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), params["w1"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(h)
        h = jax.lax.conv_general_dilated(
            h, params["w2"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.lax.pmean(jnp.mean(h.astype(jnp.float32)), "hvd")

    fwd = jax.jit(shard_map(per_shard, mesh=mesh,
                            in_specs=(P(), P("hvd")), out_specs=P()))

    rng = np.random.RandomState(0)
    host_batches = [rng.rand(global_batch, img, img, 3)
                    .astype(np.float32) for _ in range(8)]

    def batches():
        for i in range(steps):
            yield host_batches[i % len(host_batches)]

    # warmup compiles
    out = fwd(params, jax.device_put(host_batches[0], sharded))
    float(jax.device_get(out))

    t0 = time.perf_counter()
    for x in batches():
        out = fwd(params, jax.device_put(x, sharded))
    plain_s = _sync_elapsed(t0, out)

    t0 = time.perf_counter()
    for xd in prefetch_to_device(batches(), size=2, sharding=sharded):
        out = fwd(params, xd)
    prefetch_s = _sync_elapsed(t0, out)

    imgs = steps * global_batch
    return {"img_sec_plain": round(imgs / plain_s, 1),
            "img_sec_prefetch": round(imgs / prefetch_s, 1),
            "overlap_gain": round(plain_s / prefetch_s, 3),
            "batch_global": global_batch, "steps": steps, "img": img}


def _sync_elapsed(t0, out):
    """Elapsed seconds synchronized through a device_get of the final
    step's output."""
    import jax

    float(jax.device_get(out))
    return time.perf_counter() - t0


def pipeline_worker():
    import jax

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # give the CPU smoke a real 8-device mesh like the scaling leg
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    devices = jax.devices()
    print(json.dumps({"pipeline": _bench_pipeline(devices),
                      "platform": devices[0].platform}))


def _run_scaling(timeout=600):
    """Run the scaling harness in a CPU-forced subprocess, then attach
    the TCP-plane schedule probe (runs in-process: pure loopback
    sockets + threads, no JAX backend involved); returns the merged
    dict, or None when both legs failed."""
    line, _, _ = _run_worker_once(
        flag="--scaling-worker",
        extra_env={"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                                 " --xla_force_host_platform_device_count=8"
                                 ).strip()},
        timeout=timeout)
    result = {} if line is None else json.loads(line)
    try:
        result["tcp_plane"] = _bench_tcp_scaling()
    except Exception as exc:  # noqa: BLE001 — keep the XLA numbers
        sys.stderr.write(f"tcp-plane scaling probe failed: {exc!r}\n")
    return result or None


def _run_worker_once(extra_env=None, timeout=900, flag="--worker"):
    """Run one ``bench.py <flag>`` subprocess; returns ``(last JSON line
    or None, its output, its exit code — 124 when it timed out)``."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(os.path.dirname(
                       os.path.abspath(__file__)), ".jax_cache"))
    env.update(extra_env or {})
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=timeout)
    except subprocess.TimeoutExpired as exc:
        out = (exc.stdout or b"").decode("utf-8", "replace") \
            if isinstance(exc.stdout, bytes) else (exc.stdout or "")
        return None, out, 124
    if proc.returncode == 0:
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{") and line.endswith("}"):
                try:
                    json.loads(line)
                except json.JSONDecodeError:
                    continue  # brace-delimited log noise, keep looking
                return line, proc.stdout, 0
    return None, proc.stdout, proc.returncode


def profile_worker():
    """MFU ceiling analysis (VERDICT r3 prep): compile the ResNet step
    at bs 64 and 128, dump XLA's aggregate cost analysis (flops, bytes
    accessed, optimal seconds) + measured step time, and the same for
    the transformer leg — the per-op FLOP/time evidence for where the
    remaining time goes.  Prints one JSON object."""
    import jax

    devices = jax.devices()
    peak = _peak_flops_per_chip(devices[0])

    import horovod_tpu as hvd
    hvd.init()

    out = {"device": devices[0].device_kind,
           "peak_bf16_flops": peak, "legs": {}}
    for label, batch in (("resnet_bs64", 64), ("resnet_bs128", 128)):
        img_sec, mfu = _bench_resnet(devices, per_device_batch=batch)
        leg = {"img_sec_per_chip": round(img_sec, 2),
               "mfu": round(mfu, 4), "batch_per_chip": batch}
        # XLA's view of the compiled step — the ceiling evidence:
        # flops/peak vs optimal_seconds (compute-bound estimate)
        # vs bytes accessed/HBM bandwidth (memory-bound estimate)
        cost = _bench_resnet.last_cost_analysis
        leg["xla_cost_analysis"] = cost
        if cost.get("flops"):
            leg["compute_bound_step_ms"] = round(
                cost["flops"] / peak * 1e3, 3)
        if cost.get("bytes accessed"):
            hbm = 819e9  # v5e HBM bandwidth, bytes/s
            leg["memory_bound_step_ms"] = round(
                cost["bytes accessed"] / hbm * 1e3, 3)
        out["legs"][label] = leg
    out["legs"]["transformer"] = _bench_transformer(devices)
    hvd.shutdown()
    print(json.dumps(out))


def main():
    """Run the measurement once and exit with the worker's code.  The
    parent stays off JAX: a chip belongs to one process, and that
    process is the worker."""
    line, out, code = _run_worker_once()
    if line is None:
        sys.stderr.write(
            f"bench worker failed (exit code {code}); tail:\n"
            f"{out[-3000:]}\n")
        return code or 1
    print(_attach_scaling(line))
    return 0


def _attach_scaling(line):
    """Merge the CPU-mesh scaling harness results into the headline
    record's extra (VERDICT r2 item 10: the 8->64-chip efficiency
    measurement machinery, pre-validated on the virtual mesh).
    ``BENCH_SCALING=0`` skips it (quick smoke runs)."""
    if os.environ.get("BENCH_SCALING", "1") in ("0", "false", "no"):
        return line
    scaling = _run_scaling()
    if scaling is None:
        return line
    try:
        record = json.loads(line)
    except json.JSONDecodeError:
        return line
    record.setdefault("extra", {})["scaling"] = scaling
    if os.environ.get("BENCH_SHARDING", "1") not in ("0", "false", "no"):
        sharding = _run_sharding()
        if sharding is not None:
            record["extra"]["sharding"] = sharding
    if os.environ.get("BENCH_GROUPS", "1") not in ("0", "false", "no"):
        grp = _run_groups()
        if grp is not None:
            record["extra"]["groups"] = grp
    if os.environ.get("BENCH_RECONNECT", "1") not in ("0", "false",
                                                      "no"):
        rec = _run_reconnect()
        if rec is not None:
            record["extra"]["reconnect"] = rec
    return json.dumps(record)


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker()
    elif "--profile" in sys.argv:
        profile_worker()
    elif "--scaling-worker" in sys.argv:
        scaling_worker()
    elif "--sharding-worker" in sys.argv:
        sharding_worker()
    elif "--sharding" in sys.argv:
        result = _run_sharding()
        print(json.dumps(result if result is not None else
                         {"error": "sharding run failed"}))
        sys.exit(0 if result is not None else 1)
    elif "--groups-worker" in sys.argv:
        groups_worker()
    elif "--groups" in sys.argv:
        result = _run_groups()
        print(json.dumps(result if result is not None else
                         {"error": "groups run failed"}))
        sys.exit(0 if result is not None else 1)
    elif "--reconnect-worker" in sys.argv:
        reconnect_worker()
    elif "--reconnect" in sys.argv:
        result = _run_reconnect()
        print(json.dumps(result if result is not None else
                         {"error": "reconnect run failed"}))
        sys.exit(0 if result is not None else 1)
    elif "--checkpoint" in sys.argv:
        sys.exit(checkpoint_bench())
    elif "--pipeline" in sys.argv:
        pipeline_worker()
    elif "--scaling" in sys.argv:
        result = _run_scaling()
        print(json.dumps(result if result is not None else
                         {"error": "scaling run failed"}))
        sys.exit(0 if result is not None else 1)
    else:
        sys.exit(main())
