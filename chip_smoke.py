"""Quickest proof that horovod_tpu starts on the attached TPU, and the
checks of the device path that no cell of the benchmark makes.

    python chip_smoke.py            # one chip: device, kernels, eager
    python chip_smoke.py --chips 4  # one four-chip host: cross-chip only

One process, the only one that touches JAX.  It drives the public
surface the way a user script does — ``hvd.init()``, ``make_mesh``,
``hvd.DistributedOptimizer`` inside ``shard_map``, ``Transformer`` /
``lm_loss``, ``run_parallel`` + the eager collectives — and fails
(non-zero exit, no ``"ok"`` line) at the first phase that does not
hold.  There is no CPU branch: without a TPU it refuses to run.

Default run, in order, one JSON line each:

- ``device``   the platform is ``tpu``; versions and compile cache
- ``kernels``  flash attention, LayerNorm and softmax-xent compiled
               (``interpret=False``), forward and backward, in both
               dtypes against their references, at the language model's
               widths; flash attention and softmax-xent also at OLMoE's
               shapes
- ``eager``    allreduce / fused group / allgather / broadcast on
               device arrays through the negotiated plane

``--chips 4`` runs ``device``, then ``dp_lm`` (the language-model step
data-parallel over four chips against the same batch on one chip: a
sum for a mean, which ``adamw`` hides from the benchmark's ``correct``)
and ``eager`` with four device-ranks, and nothing else.

Whole training steps are the benchmark's (``python3 benchmark/run.py
--workload <cell>``, ``BENCHMARK.json``): ``gpt2_medium-spmd-1chip``
and ``resnet50_v15-spmd-1chip`` run the language-model and ResNet-50
steps through the same ``DistributedOptimizer`` + ``shard_map``, hold
them to a float32 reference and to a window in which nothing compiles.
"""

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# The language model of ``dp_lm`` and of ``tests/test_chip_compile.py``:
# widths and sequence are what the kernels are built for.
LM = {"vocab": 32768, "layers": 8, "d_model": 1024, "heads": 8,
      "d_ff": 4096, "seq": 2048, "batch": 8}
# The second shape the flash kernels run at in the benchmark: OLMoE's
# attention (heads of 128, 4 sequences of 4096), after RoPE and QK-norm.
OLMOE_ATTENTION = ("flash_attention_4x4096x16x128", (4, 4096, 16, 128))
# The second shape softmax-xent runs at in the benchmark: OLMoE's logits
# (4 sequences of 4096, vocabulary 50304 = 128 x 393).
OLMOE_LOGITS = ("softmax_xent_4x4096x50304", (4, 4096, 50304))
SEED = 0  # weights and data are random, made from this
# Largest |kernel - reference| over largest |reference|, references
# traced at "highest" matmul precision.  Measured on v5e (CHANGES.md,
# PR 21) with a margin of about three.
KERNEL_TOL = {"float32": 2e-2, "bfloat16": 3e-2}
# The cross-chip gradient against the one-chip gradient of the same
# batch: bf16 activations, rows summed in another order.
DP_TOL = 5e-2


class SmokeFailure(Exception):
    """A phase did not hold; the message says what was seen."""


def compile_cache_dir():
    """Where compiled programs are kept: the directory the environment
    names, else ``<checkout>/.jax_cache`` — never both."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def emit(phase, **fields):
    """One line for a phase that held.  Only the last line of a run that
    held throughout carries ``"ok"``."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond, message):
    if not cond:
        raise SmokeFailure(message)


# --------------------------------------------------------------- device
def phase_device(chips):
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    require(platform == "tpu",
            f"JAX found platform {platform!r}, not a TPU; this script has "
            f"no CPU path")
    require(len(devices) == chips,
            f"{len(devices)} chips attached but --chips {chips} asked for")
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    emit("device", platform=platform, kind=devices[0].device_kind,
         count=len(devices), jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         compile_cache=compile_cache_dir())
    return devices


# -------------------------------------------------------------- kernels
def phase_kernels():
    """Each Pallas kernel compiled for the chip, forward and backward,
    against its own reference."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas.flash_attention import flash_attention
    from horovod_tpu.ops.pallas.layer_norm import (layer_norm,
                                                   layer_norm_reference)
    from horovod_tpu.ops.pallas.softmax_xent import (
        softmax_xent, softmax_xent_reference)
    from horovod_tpu.parallel import reference_attention

    # widths are the model's; two sequences are enough rows to compare
    b, t, h, d = 2, LM["seq"], LM["heads"], LM["d_model"] // LM["heads"]
    keys = jax.random.split(jax.random.PRNGKey(SEED), 10)

    @jax.jit
    def rel_err(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

    def compare(kernel, reference, args, argnums, weight):
        """Largest relative error over the output and the gradients of
        ``vdot(output, weight)`` with respect to ``argnums``."""
        def run(fn):
            def weighed(*a):
                out = fn(*a)
                return jnp.vdot(out.astype(jnp.float32),
                                weight.astype(jnp.float32)), out

            (_, out), grads = jax.jit(jax.value_and_grad(
                weighed, argnums, has_aux=True))(*args)
            return [out, *grads]

        got = run(kernel)
        with jax.default_matmul_precision("highest"):
            want = run(reference)
        return max(float(rel_err(g, w)) for g, w in zip(got, want))

    def reference_by_sequence(q, k, v):
        """One sequence at a time, its scores made again in the
        backward pass: float32 ``[16, 4096, 4096]`` is 1 GiB."""
        return jax.lax.map(jax.checkpoint(lambda s: reference_attention(
            *(u[None] for u in s), causal=True)[0]), (q, k, v))

    errors = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        for label, shape in (("flash_attention", (b, t, h, d)),
                             OLMOE_ATTENTION):
            q, k, v, do = (jax.random.normal(keys[i], shape, dtype)
                           for i in range(4))
            errors[f"{label}/{name}"] = compare(
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                interpret=False),
                reference_by_sequence, (q, k, v), (0, 1, 2), do)

        x = jax.random.normal(keys[4], (b, t, LM["d_model"]), dtype)
        gamma = 1 + 0.1 * jax.random.normal(keys[5], (LM["d_model"],))
        beta = 0.1 * jax.random.normal(keys[6], (LM["d_model"],))
        errors[f"layer_norm/{name}"] = compare(
            lambda x, g, bt: layer_norm(x, g, bt, 1e-6, False),
            layer_norm_reference, (x, gamma, beta), (0, 1, 2),
            jax.random.normal(keys[7], x.shape, dtype))

        for label, shape in (("softmax_xent", (b, t, LM["vocab"])),
                             OLMOE_LOGITS):
            logits = 5 * jax.random.normal(keys[8], shape, dtype)
            labels = jax.random.randint(keys[9], shape[:-1], 0, shape[-1])
            errors[f"{label}/{name}"] = compare(
                lambda lg: softmax_xent(lg, labels, False),
                lambda lg: softmax_xent_reference(lg, labels),
                (logits,), (0,),
                jnp.full(shape[:-1], 1.0 / math.prod(shape[:-1])))

    bad = {k: e for k, e in errors.items()
           if not e <= KERNEL_TOL[k.split("/")[1]]}
    require(not bad, f"kernels off their references: {bad} of {errors}, "
                     f"tolerances {KERNEL_TOL}")
    emit("kernels", shape={"batch": b, "seq": t, "heads": h, "head_dim": d,
                           "d_model": LM["d_model"], "vocab": LM["vocab"]},
         rel_err={k: float(f"{e:.3g}") for k, e in errors.items()},
         tolerance=KERNEL_TOL)


# ------------------------------------------------------- language model
def lm_model():
    import jax.numpy as jnp

    from horovod_tpu.models import Transformer, TransformerConfig

    return Transformer(TransformerConfig(
        vocab_size=LM["vocab"], n_layers=LM["layers"],
        d_model=LM["d_model"], n_heads=LM["heads"], d_ff=LM["d_ff"],
        max_len=LM["seq"], dtype=jnp.bfloat16))


def lm_step(model, opt, mesh):
    """The user's training step: per-rank loss and ``jax.grad``, the
    gradient exchange inside ``opt.update``, one program over ``mesh``.
    ``tests/test_chip_compile.py`` compiles this same function for a
    described v5e."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import lm_loss
    from horovod_tpu.parallel._compat import shard_map

    def per_shard(params, opt_state, tokens):
        def loss_fn(p):
            return lm_loss(model.apply({"params": p}, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    return jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))


def lm_inputs(model, mesh):
    """Parameters from ``SEED`` replicated over ``mesh`` and one batch of
    random tokens split over it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = jax.jit(
        model.init, out_shardings=NamedSharding(mesh, P()))(
        jax.random.PRNGKey(SEED),
        jnp.zeros((1, LM["seq"]), jnp.int32))["params"]
    tokens = np.random.RandomState(SEED).randint(
        0, LM["vocab"], (LM["batch"], LM["seq"]))
    return params, jax.device_put(tokens, NamedSharding(mesh, P("hvd")))


# ---------------------------------------------------------------- eager
def phase_eager(devices):
    """The negotiated plane: every device is a rank on its own thread,
    each handing in device-resident arrays of its own."""
    import jax
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    n = hvd.size()
    require(n == len(devices), f"hvd.size() is {n}, not {len(devices)}")

    def contribution(rank, i):
        return np.arange(64 * (i + 1), dtype=np.float32) * (rank + 1) + i

    def per_rank(rank):
        mine = [jax.device_put(contribution(rank, i), devices[rank])
                for i in range(4)]
        rows = jax.device_put(
            np.full((rank + 1, 3), float(rank), np.float32), devices[rank])
        return {
            "sum": hvd.allreduce(mine[0], op=hvd.Sum, name="smoke.sum"),
            "group": hvd.grouped_allreduce(
                mine[1:], op=hvd.Average, name="smoke.group"),
            "gather": hvd.allgather(rows, name="smoke.gather"),
            "bcast": hvd.broadcast(mine[0], n - 1, name="smoke.bcast"),
        }

    results = basics.run_parallel(per_rank)
    want_gather = np.concatenate(
        [np.full((r + 1, 3), float(r), np.float32) for r in range(n)])
    for rank, out in enumerate(results):
        np.testing.assert_allclose(
            out["sum"], sum(contribution(r, 0) for r in range(n)))
        for i, got in enumerate(out["group"], start=1):
            np.testing.assert_allclose(
                got, np.mean([contribution(r, i) for r in range(n)], 0),
                rtol=1e-6)
        np.testing.assert_array_equal(out["gather"], want_gather)
        np.testing.assert_array_equal(out["bcast"], contribution(n - 1, 0))
        # each rank's result stays on that rank's chip
        for got in (out["sum"], *out["group"], out["gather"],
                    out["bcast"]):
            require(got.devices() == {devices[rank]},
                    f"rank {rank}'s output is on {got.devices()}, not "
                    f"{devices[rank]}")
    emit("eager", ranks=n,
         controller=type(basics._get_state().controller).__name__,
         outputs_on_own_device=True)


# ------------------------------------------------ four chips: dp vs one
def phase_dp_lm(devices):
    """Two plain-SGD steps of the language model on the same 8 sequences
    from the same weights: data-parallel over the four chips, and on
    chip 0 alone.  The parameter change must agree — it is 4 x larger
    where the exchange sums instead of averaging, which ``adamw`` would
    hide."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel._compat import shard_map

    model = lm_model()
    opt = hvd.DistributedOptimizer(optax.sgd(1e-2))

    def two_steps(mesh):
        params, tokens = lm_inputs(model, mesh)
        start_params = jax.tree.map(jnp.copy, params)
        opt_state = opt.init(params)
        step = lm_step(model, opt, mesh).lower(
            params, opt_state, tokens).compile()
        losses = []
        for _ in range(2):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(jax.device_get(loss)))
        delta = jax.tree.map(lambda a, b: a - b, params, start_params)
        return step.as_text(), params, delta, losses

    mesh4 = make_mesh({"hvd": len(devices)}, devices=devices)
    text4, params4, delta4, losses4 = two_steps(mesh4)
    n_allreduce = text4.count("all-reduce(") + text4.count(
        "all-reduce-start(")
    require(n_allreduce > 0, "four-chip step compiled without all-reduce")
    require("tpu_custom_call" in text4,
            "four-chip step has no tpu_custom_call")

    # every chip reads its OWN replica here: a spread of 0 means the four
    # copies of each parameter are bit-identical
    spread = jax.jit(shard_map(
        lambda p: jax.tree.map(
            lambda w: jnp.max(jax.lax.pmax(w, "hvd")
                              - jax.lax.pmin(w, "hvd")), p),
        mesh=mesh4, in_specs=P(), out_specs=P()))(params4)
    worst_spread = max(float(s) for s in jax.tree.leaves(spread))
    require(worst_spread == 0.0,
            f"parameters differ across chips by up to {worst_spread}")

    del params4, spread
    _, _, delta1, losses1 = two_steps(
        make_mesh({"hvd": 1}, devices=devices[:1]))
    # chip 0 holds a shard of every four-chip array; compare there
    delta4 = jax.tree.map(
        lambda a: a.addressable_shards[0].data, delta4)
    delta1 = jax.tree.map(
        lambda a: a.addressable_shards[0].data, delta1)

    @jax.jit
    def compare(d4, d1):
        errs = jax.tree.map(
            lambda a, b: jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)),
            d4, d1)
        return (jnp.max(jnp.stack(jax.tree.leaves(errs))),
                optax.global_norm(d4) / optax.global_norm(d1))

    worst, ratio = (float(v) for v in compare(delta4, delta1))
    require(worst <= DP_TOL and abs(ratio - 1) <= DP_TOL,
            f"four-chip update is not the one-chip update: worst leaf "
            f"error {worst:.3g}, norm ratio {ratio:.4f} (1 is a mean, "
            f"{len(devices)} a sum), tolerance {DP_TOL}")
    emit("dp_lm", config=LM, chips=len(devices), all_reduces=n_allreduce,
         replica_spread=worst_spread,
         losses_4chip=[round(x, 4) for x in losses4],
         losses_1chip=[round(x, 4) for x in losses1],
         update_norm_ratio=round(ratio, 5),
         worst_leaf_rel_err=float(f"{worst:.3g}"), tolerance=DP_TOL)


# ----------------------------------------------------------------- main
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the cross-chip phases, on one "
                             "four-chip host")
    args = parser.parse_args(argv)

    # before any output: next to nothing else of the repository this
    # fails here, having printed nothing
    import horovod_tpu as hvd

    try:
        devices = phase_device(args.chips)
        hvd.init()
        try:
            if args.chips == 1:
                phase_kernels()
            else:
                phase_dp_lm(devices)
            phase_eager(devices)
        finally:
            hvd.shutdown()
    except SmokeFailure as exc:
        sys.stderr.write(f"chip_smoke: FAILED: {exc}\n")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
