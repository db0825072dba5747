"""Compression numerics matrix (ISSUE 1): int8 round-trip error bounds
vs block size, XLA-fused vs TCP-ring parity on the same payloads,
bucket-key separation (compressed and uncompressed requests must not
fuse), non-float passthrough, and the SPMD optimizer paths.

Error-bound convention ("block-scaled bound"): a block-scaled int8
allreduce of p contributions passes each element through at most p + 1
quantizations (p contribution encodes + 1 result encode), each bounded
by blockmax/254, so the max absolute error is checked against 1e-2 of
the exact result's max magnitude.
"""

import threading

import numpy as np
import jax.numpy as jnp
import pytest

from horovod_tpu.common import basics
from horovod_tpu.common.compression import (Compression, INT8_BLOCK,
                                            dequantize_int8_blocks,
                                            quantize_int8_blocks,
                                            resolve_compression)

N = 8


def _per_rank(fn):
    return basics.run_parallel(fn)


def _assert_block_bound(approx, exact, rel=1e-2):
    scale = np.abs(exact).max()
    err = np.abs(np.asarray(approx, np.float64)
                 - np.asarray(exact, np.float64)).max()
    assert err <= rel * scale, f"max err {err} > {rel} * max|exact| {scale}"


# ---------------------------------------------------------------- round trip
@pytest.mark.parametrize("block", [64, 256, 1024])
def test_int8_roundtrip_error_bound_vs_block_size(block):
    x = jnp.asarray(np.random.RandomState(0).randn(4 * 1024)
                    .astype(np.float32))
    q, s = quantize_int8_blocks(x, block)
    back = dequantize_int8_blocks(q, s, block)
    # per-element bound: half a quantization step of the element's block
    step = np.repeat(np.asarray(s), block)
    assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= step / 2 + 1e-7)
    # scales: one fp32 per block, max-abs derived
    assert np.asarray(s).shape == (x.size // block,)


def test_int8_roundtrip_exact_on_zeros_and_uniform_blocks():
    x = jnp.zeros((INT8_BLOCK * 2,), jnp.float32)
    q, s = quantize_int8_blocks(x)
    assert np.array_equal(np.asarray(dequantize_int8_blocks(q, s)),
                          np.zeros(x.shape, np.float32))
    # a block of +/-127-step-aligned values round-trips exactly
    y = jnp.asarray(np.tile([127.0, -127.0], INT8_BLOCK)[:INT8_BLOCK * 2]
                    .astype(np.float32))
    q, s = quantize_int8_blocks(y)
    np.testing.assert_allclose(np.asarray(dequantize_int8_blocks(q, s)),
                               np.asarray(y), rtol=1e-6)


def test_resolve_compression_surface():
    assert resolve_compression(None, default="int8") == "int8"
    assert resolve_compression("BF16") == "bf16"
    assert resolve_compression(Compression.int8) == "int8"
    assert resolve_compression(Compression.none) == "none"
    with pytest.raises(ValueError):
        resolve_compression("zstd")


# ------------------------------------------------------------ XLA fused plane
def test_int8_allreduce_xla_fused_sum_and_average(hvd):
    size = 1 << 14
    data = [np.random.RandomState(r).randn(size).astype(np.float32)
            for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)

    def fn(r):
        s = hvd.allreduce(jnp.asarray(data[r]), op=hvd.Sum,
                          name="int8.sum", compression="int8")
        a = hvd.allreduce(jnp.asarray(data[r]), op=hvd.Average,
                          name="int8.avg", compression=Compression.int8)
        return np.asarray(s), np.asarray(a)

    for s, a in _per_rank(fn):
        _assert_block_bound(s, exact)
        _assert_block_bound(a, exact / N)


def test_int8_allreduce_prescale_postscale(hvd):
    data = [np.random.RandomState(100 + r).randn(4096).astype(np.float32)
            for r in range(N)]
    exact = np.sum(np.stack(data, 0) * 0.5, 0) * 2.0

    def fn(r):
        return np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name="int8.scaled",
            prescale_factor=0.5, postscale_factor=2.0, compression="int8"))

    for out in _per_rank(fn):
        _assert_block_bound(out, exact)


def test_bf16_allreduce_xla_fused(hvd):
    data = [np.random.RandomState(10 + r).randn(4096).astype(np.float32)
            for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)

    def fn(r):
        return np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name="bf16.sum",
            compression="bf16"))

    for out in _per_rank(fn):
        # bf16 keeps ~8 mantissa bits: 2% of max is a generous envelope
        _assert_block_bound(out, exact, rel=2e-2)


def test_non_float_passthrough_exact(hvd):
    data = [(np.arange(512) * (r + 1)).astype(np.int32) for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)

    def fn(r):
        return np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name="int8.intpass",
            compression="int8"))

    for out in _per_rank(fn):
        assert np.array_equal(out, exact)


def test_tiny_tensor_passthrough_exact(hvd):
    # below one scale block the quantized path is skipped entirely
    data = [np.full((8,), r + 0.25, np.float32) for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)

    def fn(r):
        return np.asarray(hvd.allreduce(
            jnp.asarray(data[r]), op=hvd.Sum, name="int8.tiny",
            compression="int8"))

    for out in _per_rank(fn):
        np.testing.assert_allclose(out, exact, rtol=1e-6)


# -------------------------------------------------------- bucket separation
def test_bucket_key_separates_compression():
    from horovod_tpu.ops.python_controller import PythonController

    base = PythonController.allreduce_bucket_key(
        np.float32, 1, 1.0, 1.0, "none")
    comp = PythonController.allreduce_bucket_key(
        np.float32, 1, 1.0, 1.0, "int8")
    assert base != comp
    # while everything else identical still fuses
    assert base == PythonController.allreduce_bucket_key(
        np.float32, 1, 1.0, 1.0, "none")


def test_compression_resolution_unanimous_and_mixed():
    from horovod_tpu.ops.python_controller import PythonController

    assert PythonController.resolve_group_compression(
        ["int8", "int8"]) == "int8"
    # disagreement (e.g. autotune mid-publication) resolves exact
    assert PythonController.resolve_group_compression(
        ["int8", "none"]) == "none"


def test_mixed_compression_same_cycle_both_correct(hvd):
    """A compressed and an uncompressed allreduce negotiated in the same
    cycles must not fuse (different wire formats) — both complete with
    their own numerics."""
    size = 2048
    data = [np.random.RandomState(30 + r).randn(size).astype(np.float32)
            for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)

    def fn(r):
        h1 = hvd.allreduce_async(jnp.asarray(data[r]), op=hvd.Sum,
                                 name="mix.q", compression="int8")
        h2 = hvd.allreduce_async(jnp.asarray(data[r]), op=hvd.Sum,
                                 name="mix.exact", compression="none")
        return np.asarray(hvd.synchronize(h1)), \
            np.asarray(hvd.synchronize(h2))

    for q, e in _per_rank(fn):
        _assert_block_bound(q, exact)
        np.testing.assert_allclose(e, exact, rtol=1e-5)


def test_signature_includes_compression():
    from horovod_tpu.common.ops_enum import RequestType
    from horovod_tpu.ops.python_controller import EagerRequest

    t = jnp.zeros((4,), jnp.float32)
    a = EagerRequest(rank=0, req_type=RequestType.ALLREDUCE, name="x",
                     tensor=t, handle=None, compression="none")
    b = EagerRequest(rank=0, req_type=RequestType.ALLREDUCE, name="x",
                     tensor=t, handle=None, compression="int8")
    assert a.signature() != b.signature()


# --------------------------------------------------------------- TCP ring
class _RingHarness:
    """In-process worker ring over real loopback TCP: one PeerService
    mailbox + RingPlane per rank, resolve_peer via MuxClient."""

    def __init__(self, p):
        from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
        from horovod_tpu.run.service import network

        self.p = p
        key = b"0" * 32
        self.services = [PeerService(key) for _ in range(p)]

        def resolver(rank):
            return network.MuxClient(
                [("127.0.0.1", self.services[rank].port)], key, timeout=30)

        self.planes = [RingPlane(r, self.services[r], resolver)
                       for r in range(p)]

    def allreduce(self, ring_id, data, **kw):
        outs = [None] * self.p
        errs = []

        def run(r):
            try:
                outs[r] = self.planes[r].allreduce(
                    ring_id, data[r], list(range(self.p)),
                    world_size=self.p, timeout=60, **kw)
            except Exception as exc:  # noqa: BLE001 — surface in the test
                errs.append(exc)

        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(self.p)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs, errs
        return outs

    def close(self):
        for plane in self.planes:
            plane.close()
        for svc in self.services:
            svc.shutdown()


@pytest.fixture(scope="module")
def ring4():
    harness = _RingHarness(4)
    yield harness
    harness.close()


def test_ring_int8_allreduce_numerics(ring4):
    p = ring4.p
    data = [np.random.RandomState(r).randn(1 << 14).astype(np.float32)
            for r in range(p)]
    exact = np.sum(np.stack(data, 0), 0)
    outs = ring4.allreduce(1001, data, op_average=False, compression="int8")
    for out in outs:
        assert out.dtype == np.float32
        _assert_block_bound(out, exact)
    # rank-consistency: every rank decodes the same blobs
    for out in outs[1:]:
        assert np.array_equal(out, outs[0])


def test_ring_bf16_allreduce_numerics(ring4):
    p = ring4.p
    data = [np.random.RandomState(50 + r).randn(8192).astype(np.float32)
            for r in range(p)]
    exact = np.sum(np.stack(data, 0), 0)
    outs = ring4.allreduce(1002, data, op_average=True, compression="bf16")
    for out in outs:
        _assert_block_bound(out, exact / p, rel=2e-2)


def test_ring_int8_int_dtype_stays_exact(ring4):
    p = ring4.p
    data = [(np.arange(4096) * (r + 1)).astype(np.int64) for r in range(p)]
    exact = np.sum(np.stack(data, 0), 0)
    outs = ring4.allreduce(1003, data, op_average=False, compression="int8")
    for out in outs:
        assert np.array_equal(out, exact)


def test_ring_int8_wire_bytes_quarter(ring4):
    """Bytes-on-wire accounting at the framing layer: the int8 ring
    must ship ~1/4 of the exact ring's wire bytes (1 byte/elem + ~1.6%
    fp32 scales vs the exact path's NATIVE fp32 4 bytes/elem — the
    exact ring wires the input dtype since the pipelined data plane,
    so the fp32-equivalent convention is the measured value itself)."""
    p = ring4.p

    def measured(ring_id, compression):
        base = [plane.bytes_sent() for plane in ring4.planes]
        ring4.allreduce(ring_id, data, op_average=False,
                        compression=compression)
        return sum(plane.bytes_sent() - b
                   for plane, b in zip(ring4.planes, base))

    data = [np.random.RandomState(r).randn(1 << 14).astype(np.float32)
            for r in range(p)]
    none_bytes = measured(1004, "none")
    int8_bytes = measured(1005, "int8")
    assert int8_bytes <= 0.30 * none_bytes, (int8_bytes, none_bytes)


def test_ring_vs_xla_fused_parity_same_payload(hvd, ring4):
    """Both data planes within the block-scaled bound of the same exact
    sum, and within 2x the bound of each other (they quantize with the
    same block size but accumulate fp32 vs fp64)."""
    p = ring4.p
    size = 1 << 14
    data = [np.random.RandomState(70 + r).randn(size).astype(np.float32)
            for r in range(p)]
    padded = data + [np.zeros(size, np.float32)] * (N - p)
    exact = np.sum(np.stack(data, 0), 0)

    ring_out = ring4.allreduce(1006, data, op_average=False,
                               compression="int8")[0]

    def fn(r):
        return np.asarray(hvd.allreduce(
            jnp.asarray(padded[r]), op=hvd.Sum, name="parity.int8",
            compression="int8"))

    xla_out = _per_rank(fn)[0]
    _assert_block_bound(ring_out, exact)
    _assert_block_bound(xla_out, exact)
    _assert_block_bound(ring_out, xla_out, rel=2e-2)


# ------------------------------------------------------------- SPMD wrappers
def test_distributed_optimizer_int8_reduces_gradients(hvd):
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map

    mesh = hvd.mesh()
    n = mesh.devices.size
    grads = np.random.RandomState(3).randn(n, 2048).astype(np.float32)
    expected = grads.mean(0)

    opt = hvd.DistributedOptimizer(optax.sgd(1.0), named_axes=("hvd",),
                                   compression=Compression.int8)

    def per_shard(g):
        state = opt.init({"w": g[0]})
        updates, _ = opt.update({"w": g[0]}, state)
        return updates["w"][None]

    out = jax.jit(shard_map(per_shard, mesh=mesh, in_specs=P("hvd"),
                            out_specs=P("hvd")))(jnp.asarray(grads))
    # sgd(1.0) updates are -mean(grad)
    _assert_block_bound(-np.asarray(out)[0], expected)


def test_sharded_optimizer_int8_reduce_scatter(hvd):
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel._compat import shard_map

    mesh = hvd.mesh()
    n = mesh.devices.size
    grads = np.random.RandomState(5).randn(n, 4096).astype(np.float32)
    expected = grads.mean(0)

    opt = hvd.ShardedDistributedOptimizer(optax.sgd(1.0),
                                          compression=Compression.int8)

    def per_shard(g):
        params = {"w": g[0]}
        state = opt.init(params)
        updates, _ = opt.update({"w": g[0]}, state, params)
        return updates["w"][None]

    out = jax.jit(shard_map(
        per_shard, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd")))(
            jnp.asarray(grads))
    _assert_block_bound(-np.asarray(out)[0], expected)


def test_allreduce_gradients_int8_multi_axis_rejected():
    from horovod_tpu.jax_api import _single_axis

    assert _single_axis(("hvd",), "x") == "hvd"
    assert _single_axis("hvd", "x") == "hvd"
    with pytest.raises(ValueError):
        _single_axis(("a", "b"), "x")


# ------------------------------------------------------------ config surface
def test_hvd_tpu_compression_env(monkeypatch):
    from horovod_tpu.common.config import Config

    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    assert Config.from_env().compression == "int8"
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "bogus")
    with pytest.raises(ValueError):
        Config.from_env()
    monkeypatch.delenv("HVD_TPU_COMPRESSION")
    assert Config.from_env().compression == "none"


def test_default_params_include_compression():
    from horovod_tpu.common.config import Config
    from horovod_tpu.ops.autotune import default_params

    cfg = Config()
    cfg.compression = "int8"
    assert default_params(cfg)["compression"] == "int8"


def test_parameter_manager_compression_knob():
    from horovod_tpu.common import autotune

    pm = autotune.ParameterManager(compression=True,
                                   compression_available=True)
    assert pm.compression_enabled is True
    pm_off = autotune.ParameterManager()
    assert pm_off.compression_enabled is False


# ----------------------------------------------------- hierarchical schedule
def test_int8_and_bf16_hierarchical_allreduce():
    """Compressed fused allreduce on the two-level (cross, local) mesh:
    quantized legs over the fast local axis, fp32 chunk across the
    cross axis (requantize only before the allgather leg)."""
    import jax

    from horovod_tpu.common.ops_enum import ReduceOp
    from horovod_tpu.ops.xla_executor import XlaExecutor

    class _Handle:
        def set_result(self, value):
            self.res = value

        def set_error(self, message):
            raise AssertionError(message)

    class _Entry:
        pass

    ex = XlaExecutor(jax.devices(), hier_local_size=4)
    ex.hierarchical_allreduce = True
    assert ex.hier_mesh is not None
    data = [np.random.RandomState(r).randn(10000).astype(np.float32)
            for r in range(N)]
    exact = np.sum(np.stack(data, 0), 0)
    for comp, rel in (("int8", 1e-2), ("bf16", 2e-2)):
        entry = _Entry()
        entry.shape = (10000,)
        entry.dtype = np.dtype(np.float32)
        entry.tensors = {r: jnp.asarray(data[r]) for r in range(N)}
        entry.handles = {r: _Handle() for r in range(N)}
        ex.allreduce_fused([entry], op=ReduceOp.SUM, prescale_factor=1.0,
                           postscale_factor=1.0, compression=comp)
        for rank in (0, 5):
            _assert_block_bound(np.asarray(entry.handles[rank].res),
                                exact, rel=rel)
