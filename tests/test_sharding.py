"""ZeRO-sharded weight update, first-class reduce-scatter, and the
NamedSharding MeshExecutor (docs/sharding.md).

Covers the full subsystem contract:

- ``zero_shard_layout`` / ``shard_chunk_size`` units against the
  ``np.array_split`` partition they promise;
- ``make_mesh`` input hardening and fsdp-axis meshes;
- eager ``hvd.reduce_scatter`` parity against numpy oracles on the
  8-rank in-process mesh (odd sizes, ``dim0 < world``, 2-D row blocks,
  Sum/Average, pre/postscale, bf16/int8 wire compression, Adasum and
  0-d rejection) plus ``grouped_allgather``;
- ``ZeroDistributedOptimizer`` numerics parity with the replicated
  update (exact-quantizing int8 leg included), the 1/N state-footprint
  guarantee, the deterministic ``min_size`` fallback, and the
  ``gather_zero_state`` / ``reshard_zero_state`` roundtrip;
- never-fuse: sharded and replicated collectives under the SAME tensor
  name must not satisfy each other's caches in any controller (native
  behavioral, tcp signature unit, python-controller subprocess);
- ``MeshExecutor`` selection via ``HVD_TPU_EXECUTOR=mesh`` in a
  subprocess: dp-axis mesh, ``named_sharding``, and the same collective
  + ZeRO numerics as the psum executor.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.common import basics
from horovod_tpu.common.config import _validated_executor
from horovod_tpu.common.handles import HvdError
from horovod_tpu.common.ops_enum import (INT8_BLOCK, RequestType, Sum,
                                         reduce_scatter_split_sizes)
from horovod_tpu.parallel.mesh import MeshAxes, make_mesh
from horovod_tpu.sharding.zero import shard_chunk_size, zero_shard_layout

N = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _per_rank(fn):
    return basics.run_parallel(fn)


# ================================================================ units ====
@pytest.mark.parametrize("n_params,world", [
    (0, 4), (1, 4), (3, 4), (8, 4), (13, 4), (1000, 3), (7, 8), (5, 1),
])
def test_zero_shard_layout_matches_array_split(n_params, world):
    oracle = [len(c) for c in np.array_split(np.arange(n_params), world)]
    offset = 0
    for rank in range(world):
        counts, off, cnt = zero_shard_layout(n_params, world, rank)
        assert list(counts) == oracle
        assert cnt == oracle[rank]
        assert off == offset
        offset += cnt
    assert offset == n_params
    assert list(counts) == list(reduce_scatter_split_sizes(n_params, world))


@pytest.mark.parametrize("world", [1, 2, 4, 8])
def test_zero_optimizer_state_is_one_over_n(world):
    """ZeRO acceptance (docs/sharding.md): the largest rank's adam state
    for its shard of a flat parameter vector is ~1/N of the replicated
    state (``np.array_split`` gives the first ranks one extra element)."""
    n_params = 1 << 20
    params = jnp.zeros((n_params,), jnp.float32)
    opt = optax.adam(1e-3)

    def nbytes(state):
        return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(state))

    largest = 0
    for rank in range(world):
        _, off, cnt = zero_shard_layout(n_params, world, rank)
        largest = max(largest, nbytes(opt.init(params[off:off + cnt])))
    ratio = largest / nbytes(opt.init(params))
    # mu+nu shard exactly; the count scalar is O(1) — allow 2% over 1/N
    assert 1.0 / world * 0.9 <= ratio <= 1.0 / world + 0.02, (world, ratio)


def test_shard_chunk_size_is_ceil_div():
    assert shard_chunk_size(8, 4) == 2
    assert shard_chunk_size(9, 4) == 3
    assert shard_chunk_size(1, 4) == 1
    assert shard_chunk_size(0, 4) == 0
    assert shard_chunk_size(5, 1) == 5


@pytest.mark.parametrize("bad", ["psums", "MESH", "", "gspmd"])
def test_validated_executor_rejects_typos(bad):
    with pytest.raises(ValueError, match="HVD_TPU_EXECUTOR"):
        _validated_executor(bad)
    assert _validated_executor("psum") == "psum"
    assert _validated_executor("mesh") == "mesh"


# ==================================================== make_mesh hardening ====
def test_make_mesh_rejects_non_int_sizes():
    devs = jax.devices()[:4]
    with pytest.raises(ValueError, match="must be an int"):
        make_mesh({MeshAxes.DP: 2.0, MeshAxes.FSDP: 2}, devices=devs)
    with pytest.raises(ValueError, match="must be an int"):
        make_mesh({MeshAxes.DP: True, MeshAxes.FSDP: 4}, devices=devs)


def test_make_mesh_rejects_zero_and_negative_sizes():
    devs = jax.devices()[:4]
    with pytest.raises(ValueError, match="must be a positive int"):
        make_mesh({MeshAxes.DP: 0, MeshAxes.FSDP: -1}, devices=devs)
    with pytest.raises(ValueError, match="must be a positive int"):
        make_mesh({MeshAxes.DP: -2}, devices=devs)
    with pytest.raises(ValueError, match="at most one axis may be -1"):
        make_mesh({MeshAxes.DP: -1, MeshAxes.FSDP: -1}, devices=devs)


def test_make_mesh_rejects_non_divisible_absorption():
    devs = jax.devices()[:8]
    with pytest.raises(ValueError, match="not divisible"):
        make_mesh({MeshAxes.DP: 3, MeshAxes.FSDP: -1}, devices=devs)


def test_make_mesh_builds_fsdp_meshes():
    devs = jax.devices()[:8]
    m = make_mesh({MeshAxes.DP: 2, MeshAxes.FSDP: 4}, devices=devs)
    assert m.axis_names == (MeshAxes.DP, MeshAxes.FSDP)
    assert m.devices.shape == (2, 4)
    m = make_mesh({MeshAxes.DP: 2, MeshAxes.FSDP: -1}, devices=devs)
    assert m.shape[MeshAxes.FSDP] == 4
    # default: flat dp mesh over everything
    m = make_mesh(devices=devs)
    assert m.axis_names == (MeshAxes.DP,) and m.devices.shape == (8,)


# ============================================== eager reduce_scatter =======
@pytest.mark.parametrize("dim0", [1, 7, 8, 13, 29])
def test_reduce_scatter_sum_odd_sizes(hvd, dim0):
    data = [np.random.RandomState(100 + r).randn(dim0).astype(np.float32)
            for r in range(N)]
    full = np.stack(data).astype(np.float64).sum(0)
    blocks = np.array_split(full, N)

    def fn(r):
        return np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Sum, name=f"rs.sum.{dim0}"))

    outs = _per_rank(fn)
    for r, out in enumerate(outs):
        assert out.shape == blocks[r].shape
        np.testing.assert_allclose(out.astype(np.float64), blocks[r],
                                   rtol=1e-5, atol=1e-5)


def test_reduce_scatter_2d_row_blocks(hvd):
    data = [np.full((11, 3), float(r + 1), np.float32) for r in range(N)]
    total = float(sum(range(1, N + 1)))
    counts = reduce_scatter_split_sizes(11, N)

    def fn(r):
        return np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Sum, name="rs.2d"))

    for r, out in enumerate(_per_rank(fn)):
        assert out.shape == (counts[r], 3)
        np.testing.assert_allclose(out, np.full((counts[r], 3), total))


def test_reduce_scatter_average_with_scaling(hvd):
    data = [np.arange(9, dtype=np.float32) * (r + 1) for r in range(N)]
    full = np.stack(data).mean(0) * 0.5 * 2.0
    blocks = np.array_split(full, N)

    def fn(r):
        return np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Average, prescale_factor=0.5,
            postscale_factor=2.0, name="rs.avg.scaled"))

    for r, out in enumerate(_per_rank(fn)):
        np.testing.assert_allclose(out, blocks[r], rtol=1e-5)


def test_reduce_scatter_bf16_wire(hvd):
    # small integers are exact in bf16, so the compressed wire must
    # reproduce the exact oracle
    data = [np.arange(17, dtype=np.float32) * (r + 1) for r in range(N)]
    full = np.stack(data).sum(0)
    blocks = np.array_split(full, N)

    def fn(r):
        return np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Sum, compression="bf16",
            name="rs.bf16"))

    for r, out in enumerate(_per_rank(fn)):
        np.testing.assert_allclose(out, blocks[r])


def test_reduce_scatter_int8_wire_block_constant_exact(hvd):
    # block-constant data quantizes exactly (one scale per block)
    nblocks = 2 * N
    base = np.repeat(np.arange(nblocks, dtype=np.float32) + 1.0, INT8_BLOCK)
    data = [base * (r + 1) for r in range(N)]
    full = base * sum(range(1, N + 1))
    blocks = np.array_split(full, N)

    def fn(r):
        return np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Sum, compression="int8",
            name="rs.int8"))

    for r, out in enumerate(_per_rank(fn)):
        np.testing.assert_allclose(out, blocks[r], rtol=1e-6)


def test_reduce_scatter_rejects_adasum(hvd):
    with pytest.raises(ValueError, match="Adasum"):
        hvd.reduce_scatter(jnp.ones((4,)), op=hvd.Adasum, name="rs.adasum")


def test_reduce_scatter_rejects_0d(hvd):
    def fn(r):
        try:
            hvd.reduce_scatter(jnp.asarray(1.0), op=hvd.Sum, name="rs.0d")
        except (HvdError, ValueError) as exc:
            return type(exc).__name__
        return None

    assert all(_per_rank(fn))


def test_grouped_allgather_variable_dim0(hvd):
    def fn(r):
        tensors = [jnp.full((r + 1,), float(r), jnp.float32),
                   jnp.full((2, 3), float(r + 10), jnp.float32)]
        return [np.asarray(t) for t in
                hvd.grouped_allgather(tensors, name="ga.group")]

    outs = _per_rank(fn)
    exp_a = np.concatenate([np.full((i + 1,), float(i), np.float32)
                            for i in range(N)])
    exp_b = np.concatenate([np.full((2, 3), float(i + 10), np.float32)
                            for i in range(N)])
    for a, b in outs:
        np.testing.assert_allclose(a, exp_a)
        np.testing.assert_allclose(b, exp_b)


# ======================================================= ZeRO optimizer ====
_LR = 0.05


def _oracle_adam(params, mean_grads_per_step):
    """The replicated update every rank would compute locally."""
    opt = optax.adam(_LR)
    st = opt.init(params)
    p = params
    for g in mean_grads_per_step:
        u, st = opt.update(g, st, p)
        p = optax.apply_updates(p, u)
    return p


def _shard_leaf_lengths(state, n_params):
    return sorted(int(l.shape[0]) for l in jax.tree_util.tree_leaves(state)
                  if getattr(l, "ndim", 0) == 1)


def test_zero_optimizer_matches_replicated_update(hvd):
    params = {"w": jnp.asarray(np.random.RandomState(0)
                               .randn(33).astype(np.float32)),
              "b": jnp.asarray(np.random.RandomState(1)
                               .randn(5, 3).astype(np.float32))}
    n_params = 33 + 15
    steps = 3
    rank_grads = [[jax.tree_util.tree_map(
        lambda p, r=r, s=s: jnp.asarray(
            np.random.RandomState(7 * r + s).randn(*p.shape)
            .astype(np.float32)), params) for s in range(steps)]
        for r in range(N)]
    mean_grads = [jax.tree_util.tree_map(
        lambda *gs: sum(gs) / N, *[rank_grads[r][s] for r in range(N)])
        for s in range(steps)]
    oracle = _oracle_adam(params, mean_grads)
    counts, _, _ = zero_shard_layout(n_params, N, 0)

    def fn(r):
        opt = hvd.ZeroDistributedOptimizer(optax.adam(_LR), min_size=1)
        st = opt.init(params)
        lens = _shard_leaf_lengths(st, n_params)
        p = params
        for g in rank_grads[r]:
            u, st = opt.update(g, st, p)
            p = optax.apply_updates(p, u)
        # gather -> reshard must be the identity on the live shard
        full = hvd.gather_zero_state(st, n_params)
        back = hvd.reshard_zero_state(full, n_params)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree_util.tree_leaves(st),
                                   jax.tree_util.tree_leaves(back)))
        full_lens = _shard_leaf_lengths(full, n_params)
        return p, lens, same, full_lens

    for r, (p, lens, roundtrip_ok, full_lens) in enumerate(_per_rank(fn)):
        # numerics: identical to the replicated update
        for k in params:
            np.testing.assert_allclose(np.asarray(p[k]),
                                       np.asarray(oracle[k]),
                                       rtol=0, atol=1e-6)
        # footprint: every 1-D state leaf is this rank's 1/N shard
        assert lens and set(lens) == {counts[r]}, (r, lens)
        assert counts[r] < n_params
        # gathered state is full-size, and resharding it returns the
        # exact live shard
        assert full_lens and set(full_lens) == {n_params}
        assert roundtrip_ok


def test_zero_optimizer_int8_wire_matches_replicated(hvd):
    # block-constant gradients quantize exactly, so the int8-compressed
    # sharded update must match the uncompressed replicated oracle
    n_params = N * INT8_BLOCK  # alignment: each rank's shard = 1 block
    params = jnp.zeros((n_params,), jnp.float32)
    steps = 2
    rank_grads = [[jnp.asarray(np.repeat(
        np.arange(N, dtype=np.float32) + 1 + r + 3 * s, INT8_BLOCK))
        for s in range(steps)] for r in range(N)]
    mean_grads = [sum(rank_grads[r][s] for r in range(N)) / N
                  for s in range(steps)]
    oracle = _oracle_adam(params, mean_grads)

    def fn(r):
        opt = hvd.ZeroDistributedOptimizer(optax.adam(_LR),
                                           compression="int8", min_size=1)
        st = opt.init(params)
        p = params
        for g in rank_grads[r]:
            u, st = opt.update(g, st, p)
            p = optax.apply_updates(p, u)
        return np.asarray(p)

    for out in _per_rank(fn):
        np.testing.assert_allclose(out, np.asarray(oracle),
                                   rtol=0, atol=1e-6)


def test_zero_min_size_falls_back_to_replicated_state(hvd):
    # below the threshold the update must keep FULL state on every rank
    # (and still match the oracle) -- the fallback is a pure function of
    # (n_params, world, min_size) so all ranks agree
    params = jnp.asarray(np.random.RandomState(3)
                         .randn(12).astype(np.float32))
    grads = [jnp.asarray(np.random.RandomState(50 + r)
                         .randn(12).astype(np.float32)) for r in range(N)]
    oracle = _oracle_adam(params, [sum(grads) / N])

    def fn(r):
        opt = hvd.ZeroDistributedOptimizer(optax.adam(_LR), min_size=10_000)
        st = opt.init(params)
        lens = _shard_leaf_lengths(st, 12)
        u, st = opt.update(grads[r], st, params)
        return np.asarray(optax.apply_updates(params, u)), lens

    for out, lens in _per_rank(fn):
        np.testing.assert_allclose(out, np.asarray(oracle),
                                   rtol=0, atol=1e-6)
        assert lens and set(lens) == {12}


# ============================================================ never-fuse ====
def test_same_name_allreduce_and_reduce_scatter_never_share_cache(hvd):
    # two rounds: the second hits the native response cache + the
    # executor's memoized programs, where a shared signature would
    # hand a reduce_scatter the cached allreduce (or vice versa)
    data = [np.arange(24, dtype=np.float32) * (r + 1) for r in range(N)]
    full = np.stack(data).sum(0)
    blocks = np.array_split(full, N)
    for _ in range(2):
        ar = _per_rank(lambda r: np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name="cachesep")))
        for out in ar:
            np.testing.assert_allclose(out, full, rtol=1e-5)
        rs = _per_rank(lambda r: np.asarray(hvd.reduce_scatter(
            jnp.asarray(data[r]), op=hvd.Sum, name="cachesep")))
        for r, out in enumerate(rs):
            assert out.shape == blocks[r].shape
            np.testing.assert_allclose(out, blocks[r], rtol=1e-5)


def test_tcp_signature_separates_request_types():
    # the tcp response cache keys on _signature: identical tensors that
    # differ ONLY in request type must never collide
    from horovod_tpu.ops.tcp_controller import CollectiveMsg, _signature

    ar = CollectiveMsg("t", 0, RequestType.ALLREDUCE, Sum, b"", (8,),
                       "float32")
    rs = CollectiveMsg("t", 0, RequestType.REDUCE_SCATTER, Sum, b"", (8,),
                       "float32")
    assert _signature(ar) != _signature(rs)
    ring = CollectiveMsg("t", 0, RequestType.REDUCE_SCATTER, Sum, b"", (8,),
                         "float32", ring=True)
    assert _signature(rs) != _signature(ring)


# ================================================== subprocess matrices ====
def _run_cpu_script(script, extra_env=None, timeout=180, devices=4):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    return proc.stdout


MESH_EXECUTOR_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.parallel.mesh import MeshAxes

hvd.init()
n = hvd.size()
assert n == 4, n

ex = basics._state.executor
assert type(ex).__name__ == "MeshExecutor", type(ex).__name__
assert tuple(ex.mesh.axis_names) == (MeshAxes.DP,), ex.mesh.axis_names
assert ex.axis == MeshAxes.DP

ns = ex.named_sharding(MeshAxes.DP)
from jax.sharding import NamedSharding, PartitionSpec
assert isinstance(ns, NamedSharding)
assert ns.spec == PartitionSpec(MeshAxes.DP), ns.spec

# collective parity on the dp-axis mesh
data = [np.arange(13, dtype=np.float32) * (r + 1) for r in range(n)]
full = np.stack(data).sum(0)

out = basics.run_parallel(lambda r: np.asarray(
    hvd.allreduce(jnp.asarray(data[r]), op=hvd.Sum, name="mesh.ar")))
for o in out:
    np.testing.assert_allclose(o, full, rtol=1e-5)

blocks = np.array_split(full, n)
out = basics.run_parallel(lambda r: np.asarray(
    hvd.reduce_scatter(jnp.asarray(data[r]), op=hvd.Sum, name="mesh.rs")))
for r, o in enumerate(out):
    assert o.shape == blocks[r].shape
    np.testing.assert_allclose(o, blocks[r], rtol=1e-5)

# ZeRO step numerics on the mesh executor == local replicated oracle
params = jnp.asarray(np.random.RandomState(0).randn(21).astype(np.float32))
grads = [jnp.asarray(np.random.RandomState(10 + r)
                     .randn(21).astype(np.float32)) for r in range(n)]
opt = optax.adam(0.05)
st0 = opt.init(params)
u, _ = opt.update(sum(grads) / n, st0, params)
oracle = np.asarray(optax.apply_updates(params, u))

def step(r):
    zopt = hvd.ZeroDistributedOptimizer(optax.adam(0.05), min_size=1)
    st = zopt.init(params)
    u, st = zopt.update(grads[r], st, params)
    return np.asarray(optax.apply_updates(params, u))

for o in basics.run_parallel(step):
    np.testing.assert_allclose(o, oracle, rtol=0, atol=1e-6)

hvd.shutdown()
print("MESH_OK", flush=True)
"""


def test_mesh_executor_selected_by_env_and_matches_psum():
    out = _run_cpu_script(MESH_EXECUTOR_SCRIPT,
                          extra_env={"HVD_TPU_EXECUTOR": "mesh"})
    assert "MESH_OK" in out


PYTHON_CONTROLLER_SCRIPT = r"""
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
n = hvd.size()
assert n == 4, n
assert type(basics._state.controller).__name__ == "PythonController"

data = [np.arange(10, dtype=np.float32) * (r + 1) for r in range(n)]
full = np.stack(data).sum(0)
blocks = np.array_split(full, n)

# interleave allreduce and reduce_scatter under ONE name, twice --
# never-fuse + per-request-type dispatch in the python controller
for _ in range(2):
    out = basics.run_parallel(lambda r: np.asarray(
        hvd.allreduce(jnp.asarray(data[r]), op=hvd.Sum, name="pync")))
    for o in out:
        np.testing.assert_allclose(o, full, rtol=1e-5)
    out = basics.run_parallel(lambda r: np.asarray(
        hvd.reduce_scatter(jnp.asarray(data[r]), op=hvd.Sum, name="pync")))
    for r, o in enumerate(out):
        assert o.shape == blocks[r].shape
        np.testing.assert_allclose(o, blocks[r], rtol=1e-5)

# grouped_allgather through the python controller
out = basics.run_parallel(lambda r: [np.asarray(t) for t in
    hvd.grouped_allgather([jnp.full((r + 1,), float(r), jnp.float32)],
                          name="py.ga")])
exp = np.concatenate([np.full((i + 1,), float(i), np.float32)
                      for i in range(n)])
for (o,) in out:
    np.testing.assert_allclose(o, exp)

hvd.shutdown()
print("PY_OK", flush=True)
"""


def test_python_controller_reduce_scatter_and_never_fuse():
    out = _run_cpu_script(PYTHON_CONTROLLER_SCRIPT,
                          extra_env={"HVD_CONTROLLER": "python"})
    assert "PY_OK" in out
