"""XLA executor internals: one-executable steady state (the ResponseCache
idea mapped onto XLA's compilation model — ``xla_executor.py`` module
doc), an allreduce response as ONE launch of that executable, compiled
alltoall (VERDICT r1 item 5), and fusion-bucket numerics at alignment
edges (reference: 64-elem alignment, ``controller.cc:358-376``)."""

import collections
import glob
import os

import jax
import ml_dtypes
import numpy as np
import jax.numpy as jnp
import pytest

from horovod_tpu.common import basics
from horovod_tpu.common.handles import Handle
from horovod_tpu.common.ops_enum import ReduceOp
from horovod_tpu.ops.python_controller import GroupEntry
from horovod_tpu.ops.xla_executor import XlaExecutor

N = 8


def _per_rank(fn):
    return basics.run_parallel(fn)


def _executor(hvd):
    return basics._get_state().executor


def test_alltoall_is_one_compiled_program_reused(hvd):
    """Steady-state alltoall compiles once (pad/exchange/unpack cached by
    splits signature) and the cache does not grow on reuse."""
    executor = _executor(hvd)
    splits = [2] * N

    def fn(r):
        data = jnp.asarray(
            np.arange(2 * N * 3, dtype=np.float32).reshape(2 * N, 3)
            + 1000 * r)
        outs = []
        for i in range(3):
            out = hvd.alltoall(data, splits=splits, name="exec.a2a")
            outs.append(np.asarray(out))
        return outs

    before = len(executor._alltoall_cache)
    results = _per_rank(fn)
    after = len(executor._alltoall_cache)
    # one new signature -> exactly one cache entry for all three calls
    assert after - before == 1
    # correctness: rank r's block from each source, stacked in source order
    for r, outs in enumerate(results):
        expected = np.concatenate([
            np.arange(2 * N * 3, dtype=np.float32).reshape(2 * N, 3)[
                2 * r:2 * r + 2] + 1000 * s
            for s in range(N)])
        for out in outs:
            np.testing.assert_allclose(out, expected)


def test_allreduce_executable_cache_stable_across_steps(hvd):
    """The training steady state — same bucket signature every step —
    must not recompile: the executor's program cache stays flat."""
    executor = _executor(hvd)

    # A single named tensor per step has a deterministic bucket signature
    # (multi-tensor bursts can legitimately split differently across
    # cycles depending on arrival timing, as in the reference).
    def step(r, s):
        return np.asarray(hvd.allreduce(
            jnp.full((1023,), float(r + s)), op=hvd.Sum, name="steady"))

    _per_rank(lambda r: step(r, 0))
    size_after_first = len(executor._allreduce_cache)
    for s in range(1, 5):
        outs = _per_rank(lambda r, s=s: step(r, s))
        expected = float(sum(r + s for r in range(N)))
        np.testing.assert_allclose(outs[0], np.full((1023,), expected))
    assert len(executor._allreduce_cache) == size_after_first


def test_fusion_alignment_edge_sizes(hvd):
    """Tensor sizes straddling the 64-element alignment boundary fuse and
    un-fuse exactly (off-by-one slicing here corrupts neighbors)."""
    sizes = [1, 63, 64, 65, 127, 128, 129]

    def fn(r):
        hs = [hvd.allreduce_async(
                  jnp.arange(n, dtype=jnp.float32) + 1000.0 * r,
                  op=hvd.Sum, name=f"edge.{n}")
              for n in sizes]
        return [np.asarray(hvd.synchronize(h)) for h in hs]

    total_rank = 1000.0 * sum(range(N))
    for outs in _per_rank(fn):
        for n, out in zip(sizes, outs):
            expected = N * np.arange(n, dtype=np.float32) + total_rank
            np.testing.assert_allclose(out, expected)


def test_single_tensor_larger_than_fusion_threshold(hvd):
    """A tensor bigger than the fusion threshold must still go through
    (its own bucket), not be dropped or split incorrectly."""
    threshold = basics._get_state().config.fusion_threshold_bytes
    n = threshold // 4 + 1024  # floats, comfortably over

    def fn(r):
        out = hvd.allreduce(jnp.ones((n,), jnp.float32) * (r + 1),
                            op=hvd.Sum, name="oversize")
        arr = np.asarray(out)
        return float(arr[0]), float(arr[-1]), arr.shape

    expected = float(sum(range(1, N + 1)))
    for first, last, shape in _per_rank(fn):
        assert shape == (n,)
        assert first == expected and last == expected


def test_dtype_flip_mid_burst_splits_buckets_correctly(hvd):
    """f32, then i32, then f32 again in one burst: buckets split on the
    dtype flips, every tensor still lands (reference FuseResponses only
    fuses dtype-homogeneous runs)."""
    def fn(r):
        specs = [("f1", jnp.float32), ("i1", jnp.int32),
                 ("f2", jnp.float32), ("i2", jnp.int32),
                 ("f3", jnp.float32)]
        hs = [hvd.allreduce_async(
                  jnp.full((9,), r + 1, dtype=dt), op=hvd.Sum, name=nm)
              for nm, dt in specs]
        return [np.asarray(hvd.synchronize(h)) for h in hs]

    expected = float(sum(range(1, N + 1)))
    for outs in _per_rank(fn):
        for out in outs:
            np.testing.assert_allclose(
                out.astype(np.float64), np.full((9,), expected))


def test_mixed_bucket_join_zeroes_only_absent_entries(hvd):
    """A fused bucket mixing entries where a rank participates in one
    tensor but not another (it joined in between) must zero ONLY the
    absent entry — never the rank's real contribution to the other
    (regression: whole-buffer zeroing dropped submitted gradients)."""
    import jax

    from horovod_tpu.common.handles import Handle
    from horovod_tpu.ops.python_controller import GroupEntry

    executor = _executor(hvd)

    def make_entry(name, tensors):
        handles = {r: Handle(name) for r in tensors}
        return GroupEntry(name=name, shape=(4,), dtype=np.float32,
                          tensors=tensors, handles=handles), handles

    # entry A: every rank contributed; entry B: rank 5 absent (joined)
    a_tensors = {r: executor.commit(jnp.full((4,), float(r + 1)), r)
                 for r in range(N)}
    b_tensors = {r: (executor.commit(jnp.full((4,), 10.0 * (r + 1)), r)
                     if r != 5 else None)
                 for r in range(N)}
    entry_a, handles_a = make_entry("mix.a", a_tensors)
    entry_b, handles_b = make_entry("mix.b", b_tensors)

    from horovod_tpu.common.ops_enum import ReduceOp
    executor.allreduce_fused([entry_a, entry_b], op=ReduceOp.SUM,
                             prescale_factor=1.0, postscale_factor=1.0)

    # A: full sum including rank 5
    expected_a = float(sum(range(1, N + 1)))
    # B: sum excluding rank 5's (absent) contribution
    expected_b = 10.0 * float(sum(r + 1 for r in range(N) if r != 5))
    for r in range(N):
        np.testing.assert_allclose(
            np.asarray(handles_a[r].wait()), np.full((4,), expected_a),
            err_msg="rank contribution to entry A was dropped")
        np.testing.assert_allclose(
            np.asarray(handles_b[r].wait()), np.full((4,), expected_b))


def test_int_allreduce_fractional_scale_and_average(hvd):
    """Fractional prescale/postscale on integer tensors must scale in
    float and cast back — not truncate the factor to 0 (regression:
    int32 * int32(0.5) zeroed every result); Average keeps the integer
    dtype (truncating division)."""
    def fn(r):
        scaled = hvd.allreduce(jnp.full((4,), 10 * (r + 1), jnp.int32),
                               op=hvd.Sum, name="int.scale",
                               prescale_factor=0.5)
        avg = hvd.allreduce(jnp.full((3,), r, jnp.int32),
                            op=hvd.Average, name="int.avg")
        return np.asarray(scaled), np.asarray(avg), avg.dtype

    total = sum(10 * (r + 1) for r in range(N))
    for scaled, avg, avg_dtype in _per_rank(fn):
        np.testing.assert_allclose(scaled, np.full((4,), total // 2))
        assert avg_dtype == jnp.int32, avg_dtype
        np.testing.assert_allclose(
            avg, np.full((3,), int(sum(range(N)) / N)))


# ------------------------------------ one response = one program launch
# A bucket of mixed ranks-of-shape at odd sizes, with a scalar and an
# empty tensor in it, and each of them alone.
BUCKET = ((7, 3), (), (129,), (0, 4), (2, 3, 5))
RESPONSES = {"scalar": ((),), "empty": ((0, 4),), "odd": ((1023,),),
             "bucket": BUCKET}
DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16,
          "int32": np.int32}


def _executor_of(ranks, hierarchical=False):
    executor = XlaExecutor(jax.devices()[:ranks],
                           hier_local_size=2 if hierarchical else None)
    executor.hierarchical_allreduce = hierarchical
    assert (executor.hier_mesh is not None) == hierarchical
    return executor


def _response(executor, shapes, data, absent=()):
    """The entries of one response: ``data[i][rank]`` is rank's array
    for entry ``i``, committed to its device; ``absent`` holds the
    ``(i, rank)`` a joined rank never handed in."""
    entries = []
    for i, shape in enumerate(shapes):
        tensors = {rank: None if (i, rank) in absent
                   else executor.commit(array, rank)
                   for rank, array in enumerate(data[i])}
        handles = {rank: Handle(f"t{i}") for rank, t in tensors.items()
                   if t is not None}
        entries.append(GroupEntry(
            name=f"t{i}", shape=shape, dtype=np.dtype(data[i][0].dtype),
            tensors=tensors, handles=handles))
    return entries


def _small_integers(shapes, dtype, ranks, seed=0):
    """Values whose sums, halves and quarters every dtype here holds
    exactly, so the reference is exact in whatever order a backend
    adds."""
    rng = np.random.RandomState(seed)
    return [[np.asarray(rng.randint(-8, 9, size=shape)).astype(dtype)
             for _ in range(ranks)] for shape in shapes]


def _reference(per_rank, op, prescale, postscale):
    """NumPy's allreduce of one entry, scaled where the executor scales:
    float32 in its own dtype, integers and bfloat16 once in float32."""
    dtype, ranks = per_rank[0].dtype, len(per_rank)
    if dtype == np.float32:
        total = sum(x * dtype.type(prescale) for x in per_rank)
        if op == ReduceOp.AVERAGE:
            total = total / dtype.type(ranks)
        return total * dtype.type(postscale)
    total = sum(x.astype(np.float32) for x in per_rank)
    factor = prescale * postscale
    if op == ReduceOp.AVERAGE:
        factor /= ranks
    return (total * np.float32(factor)).astype(dtype)


def _results(executor, entries):
    """Every handle's result as NumPy, after checking that it has the
    entry's shape and dtype and lives on its rank's device."""
    out = []
    for entry in entries:
        per_rank = {}
        for rank, handle in entry.handles.items():
            result = handle.wait(0)
            assert result.devices() == {executor.devices[rank]}
            assert result.shape == entry.shape
            assert result.dtype == entry.dtype
            per_rank[rank] = np.asarray(result)
        out.append(per_rank)
    return out


def _profiled_events(trace_dir, work):
    """Run ``work()`` under the profiler: how often each event of the
    host's lines occurred (the CPU backend's program executions and
    compilations are events there)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return collections.Counter(
        event.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for event in line.events)


@pytest.mark.parametrize("tensors", [1, 5])
@pytest.mark.parametrize("ranks", [1, 2, 4])
def test_a_response_is_one_execution_of_one_cached_program(
        ranks, tensors, tmp_path):
    """The second step's response runs ONE device program (the cached
    collective, which flattens and splits inside itself) and compiles
    nothing; with one rank it still runs it."""
    executor = _executor_of(ranks)
    shapes = ((7, 3), (129,), (2, 3, 5), (64,), (1, 1))[:tensors]
    data = [_small_integers(shapes, np.float32, ranks, seed)
            for seed in (0, 1)]
    steps = [_response(executor, shapes, step) for step in data]

    def respond(entries):
        executor.allreduce_fused(entries, op=ReduceOp.AVERAGE,
                                 prescale_factor=1.0, postscale_factor=1.0)
        for entry in entries:
            for handle in entry.handles.values():
                handle.wait(0).block_until_ready()

    respond(steps[0])  # compiles
    events = _profiled_events(tmp_path, lambda: respond(steps[1]))
    assert events["PjRtCpuExecutable::Execute"] == 1, events
    assert events["PjRtCpuClient::Compile"] == 0, events
    assert events["hvd.exec.launch"] == 1
    assert not events["hvd.exec.fuse_in"] and not events["hvd.exec.stack"]
    assert len(executor._allreduce_cache) == 1
    assert not executor._fuse_in_cache
    for per_rank, results in zip(data[1], _results(executor, steps[1])):
        expected = _reference(per_rank, ReduceOp.AVERAGE, 1.0, 1.0)
        for result in results.values():
            np.testing.assert_array_equal(result, expected)


@pytest.mark.parametrize("scales", [(1.0, 1.0), (0.5, 3.0)],
                         ids=["unscaled", "pre0.5post3"])
@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE],
                         ids=["sum", "average"])
@pytest.mark.parametrize("response", list(RESPONSES))
@pytest.mark.parametrize("ranks", [1, 2, 4])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_allreduce_fused_equals_numpy(dtype, ranks, response, op, scales):
    """The exact paths against NumPy, value for value."""
    shapes = RESPONSES[response]
    executor = _executor_of(ranks)
    data = _small_integers(shapes, DTYPES[dtype], ranks)
    entries = _response(executor, shapes, data)
    executor.allreduce_fused(entries, op=op, prescale_factor=scales[0],
                             postscale_factor=scales[1])
    for per_rank, results in zip(data, _results(executor, entries)):
        expected = _reference(per_rank, op, *scales)
        assert len(results) == ranks
        for result in results.values():
            np.testing.assert_array_equal(result, expected)


@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE],
                         ids=["sum", "average"])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_hierarchical_allreduce_fused_equals_numpy(dtype, op):
    """Reduce-scatter, cross allreduce and all-gather over (2, 2): the
    same values as the flat path."""
    executor = _executor_of(4, hierarchical=True)
    data = _small_integers(BUCKET, DTYPES[dtype], 4)
    entries = _response(executor, BUCKET, data)
    executor.allreduce_fused(entries, op=op, prescale_factor=0.5,
                             postscale_factor=3.0)
    for per_rank, results in zip(data, _results(executor, entries)):
        expected = _reference(per_rank, op, 0.5, 3.0)
        for result in results.values():
            np.testing.assert_array_equal(result, expected)


@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVERAGE],
                         ids=["sum", "average"])
@pytest.mark.parametrize("ranks,hierarchical",
                         [(2, False), (4, False), (4, True)],
                         ids=["2flat", "4flat", "4hier"])
@pytest.mark.parametrize("compression", ["bf16", "int8"])
def test_compressed_allreduce_fused_is_close_to_numpy(
        compression, ranks, hierarchical, op):
    """A bucket on the narrow wire: within the wire format's error of
    the float32 sum, over the whole flat buffer and entry by entry."""
    shapes = ((37, 31), (), (2049,), (0, 4), (5, 3, 7))
    rng = np.random.RandomState(ranks)
    data = [[np.asarray(rng.randn(*shape)).astype(np.float32)
             for _ in range(ranks)] for shape in shapes]
    executor = _executor_of(ranks, hierarchical)
    entries = _response(executor, shapes, data)
    executor.allreduce_fused(entries, op=op, prescale_factor=0.5,
                             postscale_factor=3.0, compression=compression)
    (key,) = executor._allreduce_cache
    assert key[-1] == compression
    expected = [_reference(per_rank, op, 0.5, 3.0) for per_rank in data]
    scale = max(np.abs(e).max() for e in expected if e.size)
    for want, results in zip(expected, _results(executor, entries)):
        for result in results.values():
            assert np.abs(result - want).max(initial=0) <= 2e-2 * scale


@pytest.mark.parametrize("whole_rank", [False, True],
                         ids=["some-entries", "every-entry"])
@pytest.mark.parametrize("ranks,hierarchical",
                         [(2, False), (4, False), (4, True)],
                         ids=["2flat", "4flat", "4hier"])
def test_a_joined_rank_adds_zeros_for_what_it_did_not_hand_in(
        ranks, hierarchical, whole_rank):
    """The last rank joined: before the response (absent from every
    entry) or between two submissions (absent from every other one).
    Its zeros are made once and shared by the following steps."""
    executor = _executor_of(ranks, hierarchical)
    data = _small_integers(BUCKET, np.float32, ranks)
    joined = ranks - 1
    absent = {(i, joined) for i in range(len(BUCKET))
              if whole_rank or i % 2 == 0}
    for _ in range(2):
        entries = _response(executor, BUCKET, data, absent)
        executor.allreduce_fused(entries, op=ReduceOp.SUM,
                                 prescale_factor=1.0, postscale_factor=1.0)
        for i, results in enumerate(_results(executor, entries)):
            expected = sum(x for rank, x in enumerate(data[i])
                           if (i, rank) not in absent)
            assert sorted(results) == [
                rank for rank in range(ranks) if (i, rank) not in absent]
            for result in results.values():
                np.testing.assert_array_equal(result, expected)
    # zeros for the entries that have elements, and no others
    assert len(executor._zeros_cache) == sum(
        1 for i, _ in absent if np.prod(BUCKET[i]))


def test_one_rank_average_is_the_input_bit_for_bit_on_its_device():
    """What the benchmark's eager cell holds the plane to: alone, an
    ``Average`` hands back the bits it was given, in a new array on the
    same device, and still through the program."""
    executor = _executor_of(1)
    rng = np.random.RandomState(7)
    shapes = ((64, 3, 3, 64), (1000,), ())
    data = [[np.asarray(rng.randn(*shape) * 1e-3).astype(np.float32)]
            for shape in shapes]
    for shape, arrays in zip(shapes, data):
        (entry,) = _response(executor, (shape,), [arrays])
        executor.allreduce_fused([entry], op=ReduceOp.AVERAGE,
                                 prescale_factor=1.0, postscale_factor=1.0)
        result = entry.handles[0].wait(0)
        assert result is not entry.tensors[0]
        assert result.devices() == {executor.devices[0]}
        assert np.asarray(result).tobytes() == arrays[0].tobytes()
    assert len(executor._allreduce_cache) == len(shapes)
