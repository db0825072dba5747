"""Process-mode (tcp) dtype matrix + stall/fusion/join combination tests
(reference: the dtype x device sweep of ``test/test_torch.py`` run under
``horovodrun --gloo``, and ``test_stall.py`` driven purely by env vars).

The numpy data plane keeps 64-bit types exact here (the device-rank
matrix in ``test_dtype_matrix.py`` covers the XLA-native types).

The in-process half is the ISSUE 3 parity matrix: the pipelined
multi-stream ring (native wire dtypes, segment overlap, socket
striping) against the seed-era serial f64-wire ring, across dtypes x
sizes x compression x stripes, plus the wire-byte accounting the
acceptance criterion names."""

import os
import subprocess
import sys
import tempfile
import threading

import numpy as np
import pytest

import ring_rig
from conftest import spawn_tcp_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HVDRUN = os.path.join(REPO, "bin", "hvdrun")


def _run_hvdrun(np_, script, extra_env=None, timeout=180):
    path = os.path.join(tempfile.mkdtemp(prefix="hvd_test_"),
                        "hvd_tcp_matrix_worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, HVDRUN, "-np", str(np_), sys.executable, path]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


DTYPE_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()

ALL = ["float16", "bfloat16", "float32", "float64",
       "int8", "int16", "int32", "int64", "uint8", "uint16",
       "uint32", "uint64"]

# -- allreduce sum, every dtype, star plane (exact accumulation) ---------
for dtype in ALL:
    # cast AFTER scaling: numpy promotes bf16*int to float32
    data = ((np.arange(6) + 1) * (r + 1)).astype(dtype)
    out = np.asarray(hvd.allreduce(data, op=hvd.Sum,
                                   name=f"sum.{dtype}"))
    assert str(out.dtype) == dtype, (out.dtype, dtype)
    expect = (np.arange(6) + 1).astype(np.float64) * sum(
        range(1, n + 1))
    np.testing.assert_allclose(out.astype(np.float64), expect,
                               rtol=2e-2 if "16" in dtype else 1e-9)

# int64 exactness beyond float64's 2**53 (the star plane accumulates
# integers in int64, never through floats)
big = np.array([2**60 + r], dtype=np.int64)
out = np.asarray(hvd.allreduce(big, op=hvd.Sum, name="i64exact"))
assert int(out[0]) == sum(2**60 + i for i in range(n)), int(out[0])

# -- broadcast every dtype ------------------------------------------------
for dtype in ALL:
    data = (np.arange(4) * (r + 2)).astype(dtype)
    out = np.asarray(hvd.broadcast(data, root_rank=1,
                                   name=f"bc.{dtype}"))
    np.testing.assert_allclose(
        out.astype(np.float64),
        (np.arange(4) * 3).astype(dtype).astype(np.float64))

# -- allgather with variable dims, 64-bit types ---------------------------
for dtype in ["float64", "int64", "uint32"]:
    data = np.full((r + 1, 2), r + 1).astype(dtype)
    out = np.asarray(hvd.allgather(data, name=f"ag.{dtype}"))
    expect = np.concatenate(
        [np.full((i + 1, 2), i + 1) for i in range(n)]).astype(np.float64)
    np.testing.assert_allclose(out.astype(np.float64), expect)

# -- alltoall int64 -------------------------------------------------------
t = (np.arange(2 * n) + 100 * r).astype(np.int64)
out = np.asarray(hvd.alltoall(t, name="a2a.i64"))
expect = np.concatenate(
    [np.arange(2 * r, 2 * r + 2) + 100 * src for src in range(n)])
np.testing.assert_allclose(out, expect)

# -- ring plane sweep (threshold forced to 1KB) ---------------------------
for dtype in ["float32", "float64", "int64"]:
    data = np.full((70001,), 3).astype(dtype) * (r + 1)
    out = np.asarray(hvd.allreduce(data, op=hvd.Sum,
                                   name=f"ring.{dtype}"))
    assert str(out.dtype) == dtype
    np.testing.assert_allclose(
        out.astype(np.float64),
        np.full((70001,), 3 * sum(range(1, n + 1)), np.float64))

# -- 0-d scalars over the wire -------------------------------------------
out = hvd.allreduce(np.float64(1.5), op=hvd.Sum, name="sc64")
assert np.asarray(out).ndim == 0
assert float(np.asarray(out)) == 1.5 * n

print(f"rank {r} TCP_DTYPES_OK", flush=True)
hvd.shutdown()
"""


def test_tcp_dtype_matrix_2proc():
    result = _run_hvdrun(2, DTYPE_WORKER,
                         extra_env={"HVD_TCP_RING_THRESHOLD": "1024"})
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("TCP_DTYPES_OK") == 2


STALL_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common.handles import HvdError

hvd.init()
r, n = hvd.rank(), hvd.size()
assert n == 4

# fusion-heavy traffic while rank 3 goes silent (neither submitting nor
# joining — a join would legitimately complete the collective with zero
# stand-ins): healthy collectives complete first, then the stalled name
# trips the stall inspector, which PROMOTES the stall into a coordinated
# abort (sticky — the job is over): every rank, the silent culprit
# included, must fail its next operation with the typed error naming the
# stalled tensor, not hang (reference: StallInspector shutdown promoted
# into the PR-2 abort protocol).
import time
handles = {}
for i in range(6):
    handles[i] = hvd.allreduce_async(jnp.ones((8,)) * (r + 1),
                                     op=hvd.Sum, name=f"ok{i}")
for i, h in handles.items():
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               np.full((8,), 10.0))

if r != 3:
    try:
        hvd.allreduce(jnp.ones((4,)), op=hvd.Sum, name="stalled")
        raise SystemExit("expected stall shutdown abort")
    except HvdError as exc:
        assert "stalled" in str(exc), str(exc)
else:
    time.sleep(8)  # silent through the 4s stall-shutdown window

try:
    hvd.join()
    raise SystemExit("expected the abort to poison the join barrier")
except HvdError as exc:
    assert "stalled" in str(exc), str(exc)
print(f"rank {r} STALL_ABORT_OK", flush=True)
try:
    hvd.shutdown()
except Exception:
    pass  # rank 0's exit may take the coordinator with it first
"""


def test_tcp_stall_shutdown_with_fusion_and_join_4proc():
    result = _run_hvdrun(4, STALL_WORKER, extra_env={
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "4",
    }, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("STALL_ABORT_OK") == 4
    assert "Stalled tensor" in (result.stdout + result.stderr)


GROUPED_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()

# grouped allreduce with mixed dtypes and mixed planes (some above the
# 1KB ring threshold, some below)
tensors = [
    jnp.ones((4,), jnp.float32) * (r + 1),
    jnp.ones((70000,), jnp.float32) * (r + 1),
    jnp.ones((8,), jnp.int32) * (r + 1),
    jnp.ones((70000,), jnp.float64) * (r + 1),
]
outs = hvd.grouped_allreduce(tensors, op=hvd.Sum, name="grp")
for t, out in zip(tensors, outs):
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float64),
        np.full(t.shape, float(sum(range(1, n + 1)))))

print(f"rank {r} GROUPED_OK", flush=True)
hvd.shutdown()
"""


def test_tcp_grouped_mixed_planes_4proc():
    result = _run_hvdrun(4, GROUPED_WORKER,
                         extra_env={"HVD_TCP_RING_THRESHOLD": "1024"})
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("GROUPED_OK") == 4


JOINED_RANK_WORKER = r"""
import time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
assert hvd.size() == 3

if r == 0:
    # submit, then join while rank 2 hasn't contributed yet: the
    # collective must WAIT for rank 2, not complete without it
    h = hvd.allreduce_async(jnp.full((4,), 1.0), op=hvd.Sum, name="t")
    last = hvd.join()
    out = np.asarray(hvd.synchronize(h))
elif r == 1:
    out = np.asarray(hvd.allreduce(jnp.full((4,), 2.0), op=hvd.Sum,
                                   name="t"))
    last = hvd.join()
else:
    time.sleep(1.5)  # rank 0 has joined well before this submission
    out = np.asarray(hvd.allreduce(jnp.full((4,), 4.0), op=hvd.Sum,
                                   name="t"))
    last = hvd.join()

# every contribution must be in the sum, including the joined rank 0's
np.testing.assert_allclose(out, np.full((4,), 7.0), err_msg=str(out))
print(f"rank {r} JOINED_COUNT_OK", flush=True)
hvd.shutdown()
"""


def test_tcp_joined_rank_does_not_satisfy_live_rank():
    """Regression: the coordinator counted a since-joined rank's request
    toward completion, finishing a collective without a live rank's
    contribution (silent wrong sum)."""
    result = _run_hvdrun(3, JOINED_RANK_WORKER, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr}"
    assert result.stdout.count("JOINED_COUNT_OK") == 3


ERROR_SWEEP_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common.handles import HvdError

hvd.init()
r, n = hvd.rank(), hvd.size()

# per-op cross-rank mismatch sweep over the tcp coordinator
# (reference: the error-path coverage test_torch.py runs per backend)
cases = [
    # (submit, error fragment)
    (lambda: hvd.allreduce(np.ones(2 + r % 2, np.float32), op=hvd.Sum,
                           name="e.shape"), "shape"),
    (lambda: hvd.allreduce(
        np.ones(3, np.float32 if r % 2 == 0 else np.int32), op=hvd.Sum,
        name="e.dtype"), "dtype"),
    (lambda: hvd.allreduce(np.ones(3, np.float32),
                           op=hvd.Sum if r % 2 == 0 else hvd.Average,
                           name="e.op"), "op"),
    (lambda: (hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum,
                            name="e.type") if r % 2 == 0 else
              hvd.broadcast(np.ones(3, np.float32), root_rank=0,
                            name="e.type")), "type"),
    (lambda: hvd.broadcast(np.ones(3, np.float32), root_rank=r % 2,
                           name="e.root"), "root"),
    (lambda: hvd.allgather(
        np.ones((2, 3 + r % 2), np.float32), name="e.trail"),
     "trailing"),
    (lambda: hvd.alltoall(np.ones((4, 2), np.float32),
                          splits=[2] * n, name="e.split"), "split"),
]
for submit, frag in cases:
    try:
        submit()
        raise SystemExit(f"expected HvdError for {frag}")
    except HvdError as exc:
        assert frag in str(exc).lower(), (frag, str(exc))

# every poisoned name recovers (error responses clear the entry)
out = np.asarray(hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum,
                               name="e.shape"))
np.testing.assert_allclose(out, np.full(3, float(n)))

# torch binding over the SAME tcp plane (reference: horovodrun --gloo
# pytest test_torch.py)
import torch
import horovod_tpu.torch as hvd_t
h = hvd_t.grouped_allreduce_async(
    [torch.ones(4) * (r + 1), torch.ones(2) * 10 * (r + 1)],
    op=hvd_t.Sum, name="e.tg")
outs = hvd_t.synchronize(h)
total = float(sum(range(1, n + 1)))
assert torch.allclose(outs[0], torch.full((4,), total))
assert torch.allclose(outs[1], torch.full((2,), 10 * total))
try:
    hvd_t.allreduce(torch.ones(2 + r % 2), op=hvd_t.Sum, name="e.tshape")
    raise SystemExit("expected HvdError (torch over tcp)")
except HvdError as exc:
    assert "shape" in str(exc).lower()

print(f"rank {r} TCP_ERRORS_OK", flush=True)
hvd.shutdown()
"""


def test_tcp_error_sweep_and_torch_binding_4proc():
    """Cross-rank mismatch sweep per op over the tcp coordinator, error
    recovery, and the torch binding (incl. the grouped one-handle
    contract) riding the same process-mode plane."""
    result = _run_hvdrun(4, ERROR_SWEEP_WORKER, timeout=180)
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    assert result.stdout.count("TCP_ERRORS_OK") == 4


REDUCE_SCATTER_WORKER = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r, n = hvd.rank(), hvd.size()

# star plane (small payloads): several dtypes x odd sizes; the numpy
# data plane keeps 64-bit types exact
for dtype in ["float32", "float64", "int64", "int32"]:
    for size in [7, 10]:
        data = ((np.arange(size) + 1) * (r + 1)).astype(dtype)
        out = np.asarray(hvd.reduce_scatter(data, op=hvd.Sum,
                                            name=f"rs.{dtype}.{size}"))
        assert str(out.dtype) == dtype, (out.dtype, dtype)
        full = (np.arange(size) + 1).astype(np.float64) * sum(range(1, n + 1))
        expect = np.array_split(full, n)[r]
        np.testing.assert_allclose(out.astype(np.float64), expect)

# average + prescale through the coordinator star
data = np.full(9, 2.0 * (r + 1), np.float32)
out = np.asarray(hvd.reduce_scatter(data, op=hvd.Average,
                                    prescale_factor=0.5, name="rs.avg"))
full = np.full(9, 0.5 * 2.0 * sum(range(1, n + 1)) / n)
np.testing.assert_allclose(out, np.array_split(full, n)[r], rtol=1e-6)

# ring plane (above the 1KB threshold): the share-reduce half of the
# ring allreduce, exact against a float64 oracle
for size in [70001, 20001]:
    data = np.random.RandomState(size + r).randn(size).astype(np.float32)
    out = np.asarray(hvd.reduce_scatter(data, op=hvd.Sum,
                                        name=f"rs.ring.{size}"))
    allv = np.stack([np.random.RandomState(size + i).randn(size)
                     for i in range(n)]).astype(np.float32)
    expect = np.array_split(allv.astype(np.float64).sum(0), n)[r]
    np.testing.assert_allclose(out.astype(np.float64), expect,
                               rtol=1e-4, atol=1e-4)

# ring + int8 wire compression (block-constant data quantizes exactly,
# tolerance covers the per-hop requantization)
blocks = np.repeat(np.arange(140, dtype=np.float32) + 1, 512)[:70001]
data = blocks * (r + 1)
out = np.asarray(hvd.reduce_scatter(data, op=hvd.Sum, compression="int8",
                                    name="rs.ring.int8"))
full = blocks.astype(np.float64) * sum(range(1, n + 1))
np.testing.assert_allclose(out.astype(np.float64),
                           np.array_split(full, n)[r], rtol=2e-2, atol=0.6)

# 2-D: row-block split along dim 0
data = np.full((10, 3), float(r + 1), np.float32)
out = np.asarray(hvd.reduce_scatter(data, op=hvd.Sum, name="rs.2d"))
counts = [10 // n + (1 if i < 10 % n else 0) for i in range(n)]
assert out.shape == (counts[r], 3), out.shape
np.testing.assert_allclose(
    out, np.full((counts[r], 3), float(sum(range(1, n + 1)))))

# grouped_allgather re-assembles variable-dim0 blocks (the ZeRO second
# half) through the same controller
outs = hvd.grouped_allgather([np.full((r + 1,), float(r), np.float32)],
                             name="rs.ga")
expect = np.concatenate([np.full((i + 1,), float(i), np.float32)
                         for i in range(n)])
np.testing.assert_allclose(np.asarray(outs[0]), expect)

print(f"rank {r} RS_TCP_OK", flush=True)
hvd.shutdown()
"""


def test_tcp_reduce_scatter_both_planes_4proc():
    """First-class reduce_scatter through the tcp controller: coordinator
    star for small payloads, worker ring (share-reduce half, shifted
    schedule) above the threshold, dtype fidelity, int8 wire, and the
    allgather inverse (docs/sharding.md)."""
    result = _run_hvdrun(4, REDUCE_SCATTER_WORKER,
                         extra_env={"HVD_TCP_RING_THRESHOLD": "1024"})
    assert result.returncode == 0, \
        f"stdout:\n{result.stdout}\nstderr:\n{result.stderr[-3000:]}"
    assert result.stdout.count("RS_TCP_OK") == 4


# ===================================================================
# ISSUE 3 parity matrix: pipelined multi-stream ring vs the seed ring
# (in-process, real loopback TCP — the exact transport of tcp mode).
# ===================================================================
class _PipelinedHarness:
    """One PeerService mailbox + RingPlane per rank with bulk stripes
    (the transport rig is ``ring_rig.ring_harness`` — one definition
    for this matrix, the race fixtures and the fault tests)."""

    def __init__(self, p, segment_bytes, stripes):
        self.p = p
        self.services, self.planes = ring_rig.ring_harness(
            p, segment_bytes, stripes)
        self._ring_id = 0

    def run_all(self, fn):
        outs = [None] * self.p
        errs = []

        def run(r):
            try:
                outs[r] = fn(r)
            except Exception as exc:  # noqa: BLE001 — surface in test
                errs.append(exc)

        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(self.p)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs, errs
        return outs

    def allreduce(self, data, seed=False, op_average=False, **kw):
        self._ring_id += 1
        rid = self._ring_id
        ranks = list(range(self.p))
        if seed:
            return self.run_all(lambda r: self.planes[r].allreduce_seed(
                rid, data[r], ranks, world_size=self.p, timeout=60,
                op_average=op_average, **kw))
        return self.run_all(lambda r: self.planes[r].allreduce(
            rid, data[r], ranks, world_size=self.p, timeout=60,
            op_average=op_average, **kw))

    def close(self):
        for plane in self.planes:
            plane.close()
        for svc in self.services:
            svc.shutdown()


# sub-segment, multi-segment, and odd-remainder sizes against an 8 KB
# segment (chunks of ~size/3 elements -> 1, ~10 and ~30 segments)
_PARITY_SIZES = [500, 20001, 70001]


def _assert_rank_consistent(outs):
    for out in outs[1:]:
        assert np.array_equal(np.asarray(out), np.asarray(outs[0])), \
            "ring result differs across ranks"


@pytest.mark.parametrize("stripes", [1, 2, 4])
def test_pipelined_ring_parity_matrix(stripes):
    """dtypes (fp32/bf16/fp16/int32) x sizes (sub-segment,
    multi-segment, odd remainder) x compression (none/int8/bf16):
    the pipelined ring must match the seed ring (exact legs) or the
    float64 oracle within the codec bound (compressed legs), and be
    bit-identical across ranks in every cell."""
    import ml_dtypes

    harness = _PipelinedHarness(3, segment_bytes=8192, stripes=stripes)
    try:
        for size in _PARITY_SIZES:
            fdata = [np.random.RandomState(17 * size + r).randn(size)
                     for r in range(harness.p)]
            exact = np.sum(np.stack(fdata), 0)

            # ---- exact legs: parity against the seed ring ------------
            for dtype, rtol, atol in [
                    (np.float32, 1e-4, 1e-4),
                    (ml_dtypes.bfloat16, 1e-1, 0.25),
                    (np.float16, 2e-2, 0.1)]:
                data = [d.astype(dtype) for d in fdata]
                outs = harness.allreduce(data)
                ref = harness.allreduce(data, seed=True)
                _assert_rank_consistent(outs)
                assert outs[0].dtype == np.dtype(dtype)
                np.testing.assert_allclose(
                    np.asarray(outs[0], np.float64),
                    np.asarray(ref[0], np.float64),
                    rtol=rtol, atol=atol,
                    err_msg=f"{np.dtype(dtype).name} size={size}")

            # int32: modular wire arithmetic must stay EXACT vs seed
            idata = [(np.arange(size) * (r + 1) - size // 2).astype(
                np.int32) for r in range(harness.p)]
            outs = harness.allreduce(idata)
            ref = harness.allreduce(idata, seed=True)
            _assert_rank_consistent(outs)
            assert np.array_equal(outs[0], ref[0]), f"int32 size={size}"

            # ---- compressed legs (fp32 input) ------------------------
            data = [d.astype(np.float32) for d in fdata]
            for comp, atol in [("int8", 0.5), ("bf16", None)]:
                outs = harness.allreduce(data, compression=comp)
                _assert_rank_consistent(outs)
                if atol is not None:
                    assert np.abs(
                        np.asarray(outs[0], np.float64) - exact
                    ).max() < atol, f"{comp} size={size}"
                else:
                    np.testing.assert_allclose(
                        np.asarray(outs[0], np.float64), exact,
                        rtol=3e-2, atol=0.1,
                        err_msg=f"{comp} size={size}")
    finally:
        harness.close()


def test_pipelined_ring_int_average_survives_intermediate_overflow():
    """Regression: an int32 AVERAGE whose intermediate sum exceeds
    int32's range must read the true wide total before dividing — the
    modular native wire is only exact for a pure sum, so averaged or
    postscaled integer rings widen to int64 on the wire like the seed."""
    harness = _PipelinedHarness(3, segment_bytes=4096, stripes=2)
    try:
        data = [np.full(5000, 2 ** 30, np.int32)
                for _ in range(harness.p)]
        outs = harness.allreduce(data, op_average=True)
        ref = harness.allreduce(data, seed=True, op_average=True)
        _assert_rank_consistent(outs)
        assert np.array_equal(outs[0], ref[0])
        # the true average of 3 x 2^30 is 2^30 — NOT the wrapped value
        assert outs[0][0] == 2 ** 30, outs[0][0]

        # postscale on the sum path widens too
        outs = harness.allreduce(data, postscale=0.25)
        ref = harness.allreduce(data, seed=True, postscale=0.25)
        _assert_rank_consistent(outs)
        assert np.array_equal(outs[0], ref[0])
    finally:
        harness.close()


def test_pipelined_ring_wire_bytes_half_of_seed():
    """Acceptance: the exact-path fp32 ring ships <= 0.51x the seed
    ring's wire bytes per rank, measured at the framing layer (every
    control post and bulk stripe frame counts, headers included)."""
    harness = _PipelinedHarness(4, segment_bytes=1 << 18, stripes=2)
    try:
        data = [np.random.RandomState(r).randn(1 << 18).astype(np.float32)
                for r in range(harness.p)]  # 1 MB per rank
        harness.allreduce(data)
        pipelined = [plane.bytes_sent() for plane in harness.planes]
        harness.allreduce(data, seed=True)
        seed = [plane.bytes_sent() - b
                for plane, b in zip(harness.planes, pipelined)]
        for pp, ss in zip(pipelined, seed):
            assert pp <= 0.51 * ss, (pipelined, seed)
    finally:
        harness.close()


def test_pipelined_ring_broadcast_allgather_native_dtype_bytes():
    """Satellite: broadcast and allgather ship the array's own dtype —
    wire bytes for an N-element fp32 tensor stay ~4N per hop, nowhere
    near the 8N an f64-wire plane would move."""
    harness = _PipelinedHarness(3, segment_bytes=8192, stripes=2)
    try:
        n = 50000
        arr = np.random.RandomState(3).randn(n).astype(np.float32)
        base = [plane.bytes_sent() for plane in harness.planes]
        outs = harness.run_all(lambda r: harness.planes[r].broadcast(
            7001, arr if r == 0 else None, [0, 1, 2], 0,
            shape=arr.shape, dtype="float32", timeout=60))
        for out in outs:
            assert np.array_equal(out, arr)
        sent = [plane.bytes_sent() - b
                for plane, b in zip(harness.planes, base)]
        # root + one forwarder each upload the tensor once (~4N bytes
        # + framing); the last rank sends nothing
        for moved in sent[:2]:
            assert moved < 1.15 * arr.nbytes, sent

        blocks = [np.full((r + 2, 5), r, np.float32)
                  for r in range(harness.p)]
        nb = [b.nbytes for b in blocks]
        base = [plane.bytes_sent() for plane in harness.planes]
        outs = harness.run_all(lambda r: harness.planes[r].allgather(
            7002, blocks[r], [0, 1, 2], block_nbytes=nb, timeout=60))
        for out in outs:
            for i, blob in enumerate(out):
                assert np.array_equal(
                    np.frombuffer(blob, np.float32),
                    blocks[i].reshape(-1))
        sent = [plane.bytes_sent() - b
                for plane, b in zip(harness.planes, base)]
        total_payload = sum(nb)
        for moved in sent:
            # each rank forwards every block except the one that ends
            # its rotation: < total payload + framing
            assert moved < total_payload + 2048, (sent, total_payload)
    finally:
        harness.close()


def test_pipelined_ring_adasum_native_wire_matches_oracle():
    """Satellite: adasum wires the native dtype (fp32 halves on the
    exchange + gather legs) yet still matches the numpy VHDD oracle,
    rank-consistently."""
    from horovod_tpu.ops.adasum import adasum_reference

    harness = _PipelinedHarness(4, segment_bytes=4096, stripes=2)
    try:
        data = [np.random.RandomState(40 + r).randn(3333).astype(
            np.float32) for r in range(harness.p)]
        base = [plane.bytes_sent() for plane in harness.planes]
        outs = harness.run_all(lambda r: harness.planes[r].adasum(
            7003, data[r], list(range(harness.p)), timeout=60))
        _assert_rank_consistent(outs)
        oracle = adasum_reference(data)
        np.testing.assert_allclose(
            np.asarray(outs[0], np.float64),
            np.asarray(oracle, np.float64), rtol=5e-3, atol=5e-3)
        sent = [plane.bytes_sent() - b
                for plane, b in zip(harness.planes, base)]
        # halves + gather in fp32: ~2x the vector's 4N bytes per rank
        # plus scalar rounds — an f64-wire plane would move ~2x more
        for moved in sent:
            assert moved < 3.0 * data[0].nbytes, sent
    finally:
        harness.close()


# ===================================================================
# ISSUE 12 schedule matrix: the hierarchical and rhd schedules on the
# same transport rig — parity vs the seed ring / float64 oracle,
# bitwise rank consistency, odd worlds and mixed groups, the
# mid-collective fault cell, and digest-identical elastic re-planning.
# ===================================================================
def _sched_allreduce(harness, schedule, data, groups=None, **kw):
    """One allreduce round through the named data-plane schedule."""
    harness._ring_id += 1
    rid = harness._ring_id
    ranks = list(range(harness.p))
    kw.setdefault("op_average", False)
    if schedule == "hierarchical":
        return harness.run_all(
            lambda r: harness.planes[r].allreduce_hierarchical(
                rid, data[r], ranks, groups, world_size=harness.p,
                timeout=60, **kw))
    assert schedule == "rhd"
    return harness.run_all(lambda r: harness.planes[r].allreduce_rhd(
        rid, data[r], ranks, world_size=harness.p, timeout=60, **kw))


@pytest.mark.parametrize("schedule", ["hierarchical", "rhd"])
def test_schedule_dtype_compression_parity_matrix(schedule):
    """schedule x dtype x compression cells in a non-power-of-two world
    (p=5, mixed groups [3, 2]): exact legs must match the seed ring
    within the dtype's wire tolerance (int32 exactly), compressed legs
    the float64 oracle within the codec bound, and every cell must be
    bitwise identical across ranks — the invariant that makes a
    schedule safe to swap under a running model."""
    import ml_dtypes

    harness = _PipelinedHarness(5, segment_bytes=8192, stripes=2)
    groups = [[0, 1, 2], [3, 4]]
    try:
        for size in (500, 20001):
            fdata = [np.random.RandomState(31 * size + r).randn(size)
                     for r in range(harness.p)]
            exact = np.sum(np.stack(fdata), 0)

            # ---- exact legs: parity against the seed ring ------------
            for dtype, rtol, atol in [
                    (np.float32, 1e-4, 1e-3),
                    (ml_dtypes.bfloat16, 1e-1, 0.5),
                    (np.float16, 3e-2, 0.2)]:
                data = [d.astype(dtype) for d in fdata]
                outs = _sched_allreduce(harness, schedule, data,
                                        groups=groups)
                ref = harness.allreduce(data, seed=True)
                _assert_rank_consistent(outs)
                assert outs[0].dtype == np.dtype(dtype)
                np.testing.assert_allclose(
                    np.asarray(outs[0], np.float64),
                    np.asarray(ref[0], np.float64),
                    rtol=rtol, atol=atol,
                    err_msg=f"{schedule} {np.dtype(dtype).name} "
                            f"size={size}")

            # int32: modular wire arithmetic stays EXACT vs seed
            idata = [(np.arange(size) * (r + 1) - size // 2).astype(
                np.int32) for r in range(harness.p)]
            outs = _sched_allreduce(harness, schedule, idata,
                                    groups=groups)
            ref = harness.allreduce(idata, seed=True)
            _assert_rank_consistent(outs)
            assert np.array_equal(outs[0], ref[0]), \
                f"{schedule} int32 size={size}"

            # ---- compressed legs (fp32 input) ------------------------
            # rhd accepts the knob but wires native fp32 (latency
            # regime), so its "compressed" cells are exact; the
            # hierarchical cells compose the codec across all 4 phases.
            data = [d.astype(np.float32) for d in fdata]
            for comp in ("int8", "bf16"):
                outs = _sched_allreduce(harness, schedule, data,
                                        groups=groups, compression=comp)
                _assert_rank_consistent(outs)
                tol = 0.8 if comp == "int8" else 0.4
                if schedule == "rhd":
                    tol = 1e-3
                assert np.abs(
                    np.asarray(outs[0], np.float64) - exact
                ).max() < tol, f"{schedule} {comp} size={size}"
    finally:
        harness.close()


@pytest.mark.parametrize("p,groups", [
    (3, [[0, 1], [2]]),               # odd world, singleton group
    (5, [[0, 1], [2, 3], [4]]),       # odd world, HIER_LOCAL_SIZE=2 tail
    (6, [[0, 1, 2], [3, 4, 5]]),      # even split of a non-power-of-two
    (6, [[0, 1, 2, 3], [4, 5]]),      # mixed 4+2 grouping
])
def test_schedule_odd_worlds_match_seed(p, groups):
    """Non-power-of-two worlds and odd/mixed group shapes: both new
    schedules (hierarchical over the given groups, rhd with its
    fold-in extras) must match the seed ring and stay rank-consistent
    — the shapes an elastic reconfiguration leaves behind."""
    harness = _PipelinedHarness(p, segment_bytes=8192, stripes=2)
    try:
        for size in (997, 20001):
            data = [np.random.RandomState(7 * size + r).randn(size)
                    .astype(np.float32) for r in range(p)]
            ref = harness.allreduce(data, seed=True)
            for schedule in ("hierarchical", "rhd"):
                outs = _sched_allreduce(harness, schedule, data,
                                        groups=groups)
                _assert_rank_consistent(outs)
                np.testing.assert_allclose(
                    np.asarray(outs[0], np.float64),
                    np.asarray(ref[0], np.float64),
                    rtol=1e-4, atol=1e-3,
                    err_msg=f"{schedule} p={p} size={size}")
    finally:
        harness.close()


def test_hierarchical_average_prescale_postscale():
    """The op/scale surface composes with the two-level plan: average
    divides the wide total once, pre/postscale apply at the ends, all
    rank-consistently (the widened-wire rule the flat ring follows)."""
    harness = _PipelinedHarness(4, segment_bytes=4096, stripes=2)
    groups = [[0, 1], [2, 3]]
    try:
        data = [np.random.RandomState(60 + r).randn(4001).astype(
            np.float32) for r in range(4)]
        exact = np.sum(np.stack([d.astype(np.float64) for d in data]), 0)
        outs = _sched_allreduce(harness, "hierarchical", data,
                                groups=groups, op_average=True)
        _assert_rank_consistent(outs)
        np.testing.assert_allclose(np.asarray(outs[0], np.float64),
                                   exact / 4, rtol=1e-4, atol=1e-4)
        outs = _sched_allreduce(harness, "hierarchical", data,
                                groups=groups, prescale=0.5,
                                postscale=2.0)
        _assert_rank_consistent(outs)
        np.testing.assert_allclose(np.asarray(outs[0], np.float64),
                                   exact, rtol=1e-4, atol=1e-4)
        outs = _sched_allreduce(harness, "rhd", data, op_average=True)
        _assert_rank_consistent(outs)
        np.testing.assert_allclose(np.asarray(outs[0], np.float64),
                                   exact / 4, rtol=1e-4, atol=1e-4)
    finally:
        harness.close()


def test_replan_groups_digest_identical_across_reconfig(monkeypatch):
    """Elastic acceptance: group planning is a pure function of the
    live membership (+ env override) — repeated plans and plans from
    differently-ordered membership produce digest-identical groupings,
    so every survivor of a reconfiguration executes the same plan the
    coordinator stamped."""
    import hashlib
    import json as _json

    from horovod_tpu.ops import tcp_controller

    co = object.__new__(tcp_controller.CoordinatorService)
    co._host_of = {r: f"host{r // 4}" for r in range(8)}

    def digest(groups):
        return hashlib.sha256(
            _json.dumps(groups, sort_keys=True).encode()).hexdigest()

    monkeypatch.delenv("HVD_HIER_LOCAL_SIZE", raising=False)
    full = [co._plan_groups(range(8)) for _ in range(3)]
    assert full[0] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert len({digest(g) for g in full}) == 1

    # rank 5 lost: the re-plan from surviving membership is itself
    # deterministic and keeps the host partition
    survivors = [r for r in range(8) if r != 5]
    replans = [co._plan_groups(survivors) for _ in range(3)]
    assert replans[0] == [[0, 1, 2, 3], [4, 6, 7]]
    assert len({digest(g) for g in replans}) == 1
    # membership order must not matter
    assert co._plan_groups(reversed(survivors)) == replans[0]

    # the explicit local-size override chunks the sorted membership,
    # same determinism contract
    monkeypatch.setenv("HVD_HIER_LOCAL_SIZE", "3")
    chunked = [co._plan_groups(survivors) for _ in range(3)]
    assert chunked[0] == [[0, 1, 2], [3, 4, 6], [7]]
    assert len({digest(g) for g in chunked}) == 1

    # degenerate topologies yield no two-level plan (stay flat)
    monkeypatch.delenv("HVD_HIER_LOCAL_SIZE", raising=False)
    co._host_of = {r: f"h{r}" for r in range(4)}   # one rank per host
    assert co._plan_groups(range(4)) is None
    co._host_of = {r: "h0" for r in range(4)}      # all one host
    assert co._plan_groups(range(4)) is None
    co._host_of = {}                               # unknown topology
    assert co._plan_groups(range(4)) is None


HIER_FAULT_WORKER = r"""
import os, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
r = hvd.rank()
t = jnp.ones((70000,)) * (r + 1)
start = time.monotonic()
try:
    hvd.allreduce(t, op=hvd.Sum, name="hier.ft")
    print(f"rank {r} COMPLETED", flush=True)
except hvd.HvdAbortedError as exc:
    elapsed = time.monotonic() - start
    from horovod_tpu.common import basics
    svc = basics._get_state().controller._peer_service
    leaked = len(svc._mailbox) if svc is not None else 0
    print(f"rank {r} ABORTED origin={exc.origin_rank} "
          f"elapsed={elapsed:.1f} leaked={leaked}", flush=True)
print(f"rank {r} DONE", flush=True)
"""


def test_hierarchical_crash_mid_collective_aborts_all_ranks():
    """ISSUE 12 fault cell: rank 2 dies AFTER the coordinator stamped a
    hierarchical ring_go — peers in BOTH groups are committed (blocked
    in phase recvs / on the delegate ring).  Liveness converts the
    silence into one coordinated abort: every survivor wakes with the
    typed error naming origin=2, well inside the deadline, mailbox
    clean."""
    results = spawn_tcp_ranks(4, HIER_FAULT_WORKER, extra_env={
        "JAX_PLATFORMS": "cpu",
        "HVD_TPU_SCHEDULE": "hierarchical",
        "HVD_HIER_LOCAL_SIZE": "2",
        "HVD_TCP_RING_THRESHOLD": "1024",
        "HVD_TPU_HEARTBEAT_INTERVAL": "0.25",
        "HVD_TPU_LIVENESS_TIMEOUT": "2",
        "HVD_TPU_ABORT_TIMEOUT": "10",
        "HVD_STALL_CHECK_TIME_SECONDS": "1",
        # keep the ring recv timeout far beyond liveness so the typed
        # abort, not a local TimeoutError, wakes the blocked phases
        "HVD_STALL_SHUTDOWN_TIME_SECONDS": "30",
        "HVD_TPU_FAULT_SPEC": "rank2:ring:1:crash",
    })
    assert results[2][0] == 1, f"crashed rank: {results[2][1]}"
    for r in (0, 1, 3):
        code, out, err = results[r]
        assert code == 0, f"rank {r}: {out}\n{err[-2000:]}"
        line = next(l for l in out.splitlines()
                    if l.startswith(f"rank {r} ABORTED"))
        fields = dict(kv.split("=") for kv in line.split()[3:])
        assert fields["origin"] == "2", line
        assert float(fields["elapsed"]) < 10.0, line
        assert fields["leaked"] == "0", line


def test_resolve_schedule_bands_and_fallbacks(monkeypatch):
    """The coordinator's auto resolution: rhd owns the [8KB, 256KB]
    latency band (below it the star's single fused round-trip wins),
    hierarchical needs a viable grouping, disagreeing requests fall
    back to auto instead of fusing, and forced-but-infeasible choices
    degrade to the flat ring."""
    from types import SimpleNamespace

    from horovod_tpu.ops import tcp_controller
    from horovod_tpu.ops.tcp_dataplane import (DEFAULT_RHD_MAX_BYTES,
                                               DEFAULT_RHD_MIN_BYTES)

    monkeypatch.delenv("HVD_HIER_LOCAL_SIZE", raising=False)
    co = object.__new__(tcp_controller.CoordinatorService)
    co._published = None
    co._host_of = {r: f"h{r // 2}" for r in range(4)}

    def resolve(nbytes, scheds=("auto",) * 4):
        reqs = {i: SimpleNamespace(schedule=s)
                for i, s in enumerate(scheds)}
        return co._resolve_schedule(reqs, list(range(4)), nbytes)

    # auto: the rhd band has a floor AND a ceiling (both inclusive)
    assert resolve(DEFAULT_RHD_MIN_BYTES)[0] == "rhd"
    assert resolve(DEFAULT_RHD_MAX_BYTES)[0] == "rhd"
    sched, groups = resolve(DEFAULT_RHD_MIN_BYTES - 1)
    assert (sched, groups) == ("hierarchical", [[0, 1], [2, 3]])
    assert resolve(DEFAULT_RHD_MAX_BYTES + 1)[0] == "hierarchical"
    # rhd carries no groups
    assert resolve(DEFAULT_RHD_MIN_BYTES)[1] is None
    # forced hierarchical keeps its groups whatever the size
    sched, groups = resolve(1 << 10, scheds=("hierarchical",) * 4)
    assert (sched, groups) == ("hierarchical", [[0, 1], [2, 3]])
    # disagreeing requests fall back to auto resolution, never fuse a
    # mixed plan (here: large payload + topology -> hierarchical)
    assert resolve(1 << 20, scheds=("rhd", "flat_ring", "auto", "auto")
                   )[0] == "hierarchical"

    # no topology: everything outside the band is the flat ring, and a
    # forced hierarchical degrades to it
    co._host_of = {}
    assert resolve(DEFAULT_RHD_MAX_BYTES + 1)[0] == "flat_ring"
    assert resolve(1 << 10)[0] == "flat_ring"
    assert resolve(1 << 20, scheds=("hierarchical",) * 4
                   )[0] == "flat_ring"
    # "star" reaching a ring round (tuned-value propagation race) runs
    # the flat ring rather than desyncing
    assert resolve(1 << 20, scheds=("star",) * 4)[0] == "flat_ring"
