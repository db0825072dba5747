"""SPMD training-path tests: DistributedOptimizer over a shard_map'd step
(the TPU-native hot path replacing the reference's DistributedOptimizer +
background allreduce)."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel._compat import shard_map
from horovod_tpu.models import MLP
from horovod_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"hvd": 8})


def _loss_fn(model, params, x, y):
    logits = model.apply(params, x)
    return jnp.mean((logits - y) ** 2)


def test_distributed_optimizer_syncs_and_learns(hvd_init, mesh):
    model = MLP(features=(16, 4))
    rng = jax.random.PRNGKey(0)
    x_all = jax.random.normal(rng, (64, 8))
    y_all = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
    params = model.init(jax.random.PRNGKey(2), x_all[:1])

    opt = hvd.DistributedOptimizer(optax.sgd(0.05), named_axes=("hvd",))
    opt_state = opt.init(params)

    def per_shard_step(params, opt_state, x, y):
        grads = jax.grad(lambda p: _loss_fn(model, p, x, y))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = jax.jit(shard_map(
        per_shard_step, mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P()),
    ))

    sharded = NamedSharding(mesh, P("hvd"))
    x_all = jax.device_put(x_all, sharded)
    y_all = jax.device_put(y_all, sharded)

    loss_before = _loss_fn(model, params, x_all, y_all)
    for _ in range(20):
        params, opt_state = step(params, opt_state, x_all, y_all)
    loss_after = _loss_fn(model, params, x_all, y_all)
    assert float(loss_after) < float(loss_before)

    # replicated params must be identical on every device
    leaf = jax.tree.leaves(params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_distributed_optimizer_matches_manual_pmean(hvd_init, mesh):
    """Wrapped optimizer == manual pmean + plain optimizer."""
    params = {"w": jnp.arange(8.0)}

    def grads_for(r):
        return {"w": jnp.full((8,), float(r))}

    opt = hvd.DistributedOptimizer(optax.sgd(1.0), named_axes=("hvd",))
    state = opt.init(params)

    def shard_update(params, state, rank_arr):
        g = {"w": jnp.broadcast_to(rank_arr.reshape(()).astype(jnp.float32),
                                   (8,))}
        updates, state = opt.update(g, state, params)
        return optax.apply_updates(params, updates)

    ranks = jax.device_put(
        jnp.arange(8.0).reshape(8, 1), NamedSharding(mesh, P("hvd")))
    out = jax.jit(shard_map(
        shard_update, mesh=mesh,
        in_specs=(P(), P(), P("hvd")), out_specs=P(),
    ))(params, state, ranks)

    mean_grad = np.mean([np.full((8,), float(r)) for r in range(8)], axis=0)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.arange(8.0) - mean_grad, rtol=1e-6)


# ---- the gradient exchange is a MEAN of per-rank gradients ----------
#
# CPU witness of the N x gradient defect: under a checked jax.shard_map,
# jax.grad already sums the gradient of a P() parameter over the axis, so
# the optimizer's pmean was the identity and a 4-rank step applied 4 x
# the mean (tests that only watch the loss go down cannot see it).

_N = 4
_DIM = 512   # >= one int8 scale block, so Compression.int8 quantizes


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh({"hvd": _N}, devices=jax.devices()[:_N])


@pytest.fixture(scope="module")
def exchange_case():
    """Replicated ``w``, a global batch, and this loss's gradients: the
    single-device gradient of the whole batch and each rank's own."""
    rng = np.random.RandomState(3)
    x = rng.randn(4 * _N, _DIM).astype(np.float32)
    w = rng.randn(_DIM).astype(np.float32)

    def loss(w, x):
        return jnp.mean(jnp.tanh(x @ w))

    global_grad = np.asarray(jax.grad(loss)(w, x))
    rank_grads = [np.asarray(jax.grad(loss)(w, xs))
                  for xs in np.split(x, _N)]
    return loss, w, x, global_grad, rank_grads


def _one_update(mesh, sm, opt, case):
    loss, w, x, _, _ = case

    def per_shard(w, x):
        grads = jax.grad(loss)(w, x)
        updates, _ = opt.update(grads, opt.init(w), w)
        return updates

    fn = jax.jit(sm(per_shard, mesh=mesh, in_specs=(P(), P("hvd")),
                    out_specs=P()))
    return np.asarray(fn(w, jax.device_put(
        x, NamedSharding(mesh, P("hvd")))))


@pytest.mark.parametrize("op,scale", [(hvd.Average, 1.0),
                                      (hvd.Sum, float(_N))],
                         ids=["average", "sum"])
def test_update_is_mean_of_rank_gradients(hvd_init, mesh4, exchange_case,
                                          op, scale):
    """sgd(1.0) under DistributedOptimizer: the update is minus the
    single-device gradient of the global batch (Average), N x (Sum)."""
    global_grad = exchange_case[3]
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), op=op)
    np.testing.assert_allclose(
        _one_update(mesh4, shard_map, opt, exchange_case),
        -scale * global_grad, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["int8", "adasum"])
def test_int8_and_adasum_see_rank_gradients(hvd_init, mesh4,
                                            exchange_case, kind):
    """The quantized and Adasum reductions combine each rank's OWN
    gradient: int8 lands on the mean within its quantization bound (a
    pre-summed input would land on N x), Adasum on the pairing-tree
    oracle of the per-rank gradients (identical inputs would return the
    input)."""
    from horovod_tpu.common.compression import Compression
    from horovod_tpu.ops.adasum import adasum_reference

    _, _, _, global_grad, rank_grads = exchange_case
    if kind == "int8":
        opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                       compression=Compression.int8)
        expected = global_grad
        # two quantizations of block-max/127 each, averaged over N
        tol = 2 * np.abs(np.stack(rank_grads)).max() / 127
    else:
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), op=hvd.Adasum)
        expected, tol = adasum_reference(rank_grads), 1e-6
    got = -_one_update(mesh4, shard_map, opt, exchange_case)
    np.testing.assert_allclose(got, expected, atol=tol, rtol=1e-4)
    assert np.abs(got - expected).max() < \
        0.1 * np.abs(got - _N * global_grad).max()


def test_checked_shard_map_gradients_are_refused(hvd_init, mesh4,
                                                 exchange_case):
    """A step wrapped in plain (checked) jax.shard_map hands over a
    gradient autodiff already summed; the reduction refuses it at trace
    time instead of applying N x the mean."""
    opt = hvd.DistributedOptimizer(optax.sgd(1.0))
    with pytest.raises(ValueError, match="already reduced over mesh axes"):
        _one_update(mesh4, jax.shard_map, opt, exchange_case)


def test_backward_passes_per_step_aggregation(hvd_init, mesh):
    """Gradients accumulate locally for k passes, one reduction per k
    (reference: gradient_aggregation.py semantics)."""
    k = 4
    params = {"w": jnp.zeros((4,))}
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), named_axes=(),
                                   backward_passes_per_step=k)
    state = opt.init(params)

    @jax.jit
    def micro(params, state, g):
        updates, state = opt.update({"w": g}, state, params)
        return optax.apply_updates(params, updates), state

    for i in range(k):
        params, state = micro(params, state, jnp.full((4,), float(i + 1)))
    # mean of 1..4 = 2.5, applied once
    np.testing.assert_allclose(np.asarray(params["w"]),
                               -np.full((4,), 2.5), rtol=1e-6)


def test_allreduce_gradients_compression(hvd_init, mesh):
    from horovod_tpu.common.compression import Compression

    grads = {"a": jnp.full((8, 4), 3.0)}

    def body(g):
        return hvd.allreduce_gradients(g, named_axes=("hvd",),
                                       compression=Compression.bf16)

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("hvd"),), out_specs=P("hvd"),
    ))(grads)
    assert out["a"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["a"]), np.full((8, 4), 3.0))


def test_adasum_spmd_matches_reference(hvd_init, mesh):
    from horovod_tpu.ops.adasum import adasum_reference

    rng = np.random.RandomState(7)
    per_rank = rng.randn(8, 16).astype(np.float32)
    expected = adasum_reference(list(per_rank))

    def body(g):
        return hvd.allreduce_gradients({"g": g}, named_axes=("hvd",),
                                       op=hvd.Adasum)["g"]

    data = jax.device_put(jnp.asarray(per_rank),
                          NamedSharding(mesh, P("hvd")))
    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("hvd"),), out_specs=P(),
    ))(data.reshape(8, 1, 16))
    np.testing.assert_allclose(np.asarray(out).reshape(-1), expected,
                               rtol=1e-4, atol=1e-5)


def test_adasum_vhdd_matches_reference(hvd_init):
    """ppermute-based vector-halving distance-doubling Adasum (the
    large-tensor path, reference: adasum.h:194-330) must agree with the
    numpy pairing-tree oracle, including a length that needs padding."""
    from horovod_tpu.ops.adasum import adasum_reference, adasum_vhdd
    from horovod_tpu.parallel import make_mesh

    mesh = make_mesh({"x": 8})
    for n in (64, 37):  # 37: not divisible by 8, exercises padding
        rng = np.random.RandomState(11 + n)
        per_rank = rng.randn(8, n).astype(np.float32)
        expected = adasum_reference(list(per_rank))

        out = jax.jit(shard_map(
            lambda g: adasum_vhdd(g[0], "x")[None],
            mesh=mesh, in_specs=(P("x"),), out_specs=P(),
        ))(jnp.asarray(per_rank).reshape(8, 1, n))
        np.testing.assert_allclose(np.asarray(out).reshape(-1), expected,
                                   rtol=1e-4, atol=1e-5)


def test_adasum_hierarchical_matches_reference(hvd_init):
    """RS(local sum) -> VHDD(cross) -> AG(local) with the local_size
    divisor equals adasum(per-group averages) (reference:
    adasum_gpu_operations.cc + divisor semantics torch/mpi_ops.py:110)."""
    from horovod_tpu.ops.adasum import (adasum_reduce_hierarchical,
                                        adasum_reference)
    from horovod_tpu.parallel import make_mesh

    mesh = make_mesh({"cross": 2, "local": 4})
    rng = np.random.RandomState(13)
    per_rank = rng.randn(8, 33).astype(np.float32)  # 33: padding path
    group_a = per_rank[:4].sum(axis=0) / 4.0
    group_b = per_rank[4:].sum(axis=0) / 4.0
    expected = adasum_reference([group_a, group_b])

    out = jax.jit(shard_map(
        lambda g: adasum_reduce_hierarchical(g[0])[None],
        mesh=mesh, in_specs=(P(("cross", "local")),), out_specs=P(),
    ))(jnp.asarray(per_rank).reshape(8, 1, 33))
    np.testing.assert_allclose(np.asarray(out).reshape(-1), expected,
                               rtol=1e-4, atol=1e-5)


def test_broadcast_parameters(hvd_init):
    from horovod_tpu.common import basics

    def fn(r):
        params = {"w": jnp.full((4,), float(r)), "b": jnp.full((2,), 10.0 * r)}
        return jax.tree.map(np.asarray, hvd.broadcast_parameters(params, 0))

    for out in basics.run_parallel(fn):
        np.testing.assert_allclose(out["w"], np.zeros(4))
        np.testing.assert_allclose(out["b"], np.zeros(2))


def test_sharded_optimizer_matches_unsharded(hvd_init, mesh):
    """ZeRO-1 (ShardedDistributedOptimizer): reduce-scatter + sharded
    Adam + all-gather must produce numerically the same step as the
    replicated DistributedOptimizer (Adam is elementwise), while each
    replica holds only ~1/8 of the optimizer state."""
    model = MLP(features=(16, 4))
    x_all = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    y_all = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
    params = model.init(jax.random.PRNGKey(2), x_all[:1])
    n_params = sum(p.size for p in jax.tree.leaves(params))

    sharded = hvd.ShardedDistributedOptimizer(optax.adam(1e-2),
                                              axis_name="hvd")
    plain = hvd.DistributedOptimizer(optax.adam(1e-2),
                                     named_axes=("hvd",))
    plain_state = plain.init(params)

    def sharded_step(params, x, y):
        grads = jax.grad(lambda p: _loss_fn(model, p, x, y))(params)
        state = sharded.init(params)
        updates, state = sharded.update(grads, state, params)
        new_params = optax.apply_updates(params, updates)
        # expose my state shard so the test can check its size
        return new_params, state[0].mu if hasattr(state[0], "mu") \
            else jax.tree.leaves(state)[0]

    def plain_step(params, state, x, y):
        grads = jax.grad(lambda p: _loss_fn(model, p, x, y))(params)
        updates, state = plain.update(grads, state, params)
        return optax.apply_updates(params, updates)

    sharded_fn = jax.jit(shard_map(
        sharded_step, mesh=mesh,
        in_specs=(P(), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"))))
    plain_fn = jax.jit(shard_map(
        plain_step, mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=P()))

    sharded_params, mu_gathered = sharded_fn(params, x_all, y_all)
    plain_params = plain_fn(params, plain_state, x_all, y_all)

    for a, b in zip(jax.tree.leaves(sharded_params),
                    jax.tree.leaves(plain_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # each replica's Adam mu is the padded 1/8 chunk, not the full vector
    chunk = hvd.shard_chunk_size(n_params, 8)
    assert mu_gathered.size == 8 * chunk
    assert chunk < n_params


def test_sharded_optimizer_trains(hvd_init, mesh):
    """Multi-step training with persistent sharded state converges."""
    model = MLP(features=(16, 4))
    x_all = jax.random.normal(jax.random.PRNGKey(3), (64, 8))
    y_all = jax.random.normal(jax.random.PRNGKey(4), (64, 4))
    params = model.init(jax.random.PRNGKey(5), x_all[:1])

    opt = hvd.ShardedDistributedOptimizer(optax.adam(5e-2),
                                          axis_name="hvd")

    # the sharded state crosses the shard_map boundary as a per-rank
    # value: every leaf (including Adam's scalar count) gets a leading
    # length-1 axis inside so out_specs=P("hvd") can concatenate it
    def init_state(params):
        return hvd.sharded_state_wrap(opt.init(params))

    def step(params, state, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: _loss_fn(model, p, x, y))(params)
        updates, state = opt.update(
            grads, hvd.sharded_state_unwrap(state), params)
        return optax.apply_updates(params, updates), \
            hvd.sharded_state_wrap(state), jax.lax.pmean(loss, "hvd")

    init_fn = jax.jit(shard_map(
        init_state, mesh=mesh, in_specs=P(), out_specs=P("hvd")))

    state = init_fn(params)
    step_fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("hvd"), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"), P())))

    losses = []
    for _ in range(10):
        params, state, loss = step_fn(params, state, x_all, y_all)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6, losses


def test_sharded_optimizer_compiles_to_one_rs_one_ag(hvd_init, mesh):
    """Compiler-level contract of ZeRO-1: the whole step lowers to
    exactly ONE reduce-scatter and ONE all-gather (the gradient pytree
    is flattened first), and no all-reduce — this is the halved-traffic
    claim, checked in the compiled HLO."""
    import re

    model = MLP(features=(16, 16, 4))
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8)))
    opt = hvd.ShardedDistributedOptimizer(optax.adam(1e-2),
                                          axis_name="hvd")

    def step(p, s, x, y):
        g = jax.grad(lambda p: _loss_fn(model, p, x, y))(p)
        u, s2 = opt.update(g, hvd.sharded_state_unwrap(s), p)
        return optax.apply_updates(p, u), hvd.sharded_state_wrap(s2)

    init_j = jax.jit(shard_map(
        lambda p: hvd.sharded_state_wrap(opt.init(p)), mesh=mesh,
        in_specs=P(), out_specs=P("hvd")))
    state = init_j(params)
    step_j = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P(), P("hvd"), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"))))

    sharded = NamedSharding(mesh, P("hvd"))
    xd = jax.device_put(jnp.ones((16, 8)), sharded)
    yd = jax.device_put(jnp.ones((16, 4)), sharded)
    hlo = step_j.lower(params, state, xd, yd).compile().as_text()
    assert len(re.findall(r"reduce-scatter\(", hlo)) == 1, hlo[:500]
    assert len(re.findall(r"all-gather\(", hlo)) == 1
    assert len(re.findall(r"all-reduce\(", hlo)) == 0
