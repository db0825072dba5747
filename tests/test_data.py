"""Input pipeline: sharded batch iteration + device prefetch.

Reference analogs: torch DataLoader + DistributedSampler in the
examples (per-epoch seeded reshuffle), Petastorm reader wiring in
``horovod/spark/keras/remote.py`` (per-rank Parquet row groups,
``cur_shard=rank, shard_count=size``)."""

import numpy as np
import pytest

from horovod_tpu.utils.data import (BatchIterator, ParquetShardIterator,
                                    prefetch_to_device)
from horovod_tpu.utils.data import _leaves as _arrays, _map


def _shard(rows=20, feat=3):
    return {"x": np.arange(rows * feat, dtype=np.float32)
                   .reshape(rows, feat),
            "y": np.arange(rows, dtype=np.int32)}


def test_batch_shapes_and_count():
    it = BatchIterator(_shard(20), batch_size=8)
    batches = list(it)
    assert it.batches_per_epoch == 2
    assert len(batches) == 2
    for b in batches:
        assert b["x"].shape == (8, 3)
        assert b["y"].shape == (8,)
        # rows stay aligned across columns
        np.testing.assert_array_equal(b["x"][:, 0], b["y"] * 3)


def test_tail_batch_kept_without_drop_remainder():
    batches = list(BatchIterator(_shard(20), 8, drop_remainder=False))
    assert [len(b["y"]) for b in batches] == [8, 8, 4]
    covered = np.concatenate([b["y"] for b in batches])
    np.testing.assert_array_equal(np.sort(covered), np.arange(20))


def test_shuffle_is_seeded_and_reshuffles_per_epoch():
    a = [b["y"] for b in BatchIterator(_shard(16), 4, shuffle=True,
                                       seed=7, epochs=2)]
    b = [bb["y"] for bb in BatchIterator(_shard(16), 4, shuffle=True,
                                         seed=7, epochs=2)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)  # same seed -> same order
    epoch0 = np.concatenate(a[:4])
    epoch1 = np.concatenate(a[4:])
    assert not np.array_equal(epoch0, epoch1)  # reshuffled
    np.testing.assert_array_equal(np.sort(epoch0), np.arange(16))
    np.testing.assert_array_equal(np.sort(epoch1), np.arange(16))


def test_infinite_epochs_and_validation_errors():
    it = iter(BatchIterator(_shard(4), 2, epochs=None))
    for _ in range(10):  # > 2 epochs worth: must not stop
        next(it)
    with pytest.raises(ValueError, match="batch_size"):
        BatchIterator(_shard(4), 0)
    with pytest.raises(ValueError, match="drop_remainder"):
        BatchIterator(_shard(2), 4)
    with pytest.raises(ValueError, match="ragged"):
        BatchIterator({"x": np.zeros(3), "y": np.zeros(4)}, 1)


def test_tuple_structure_preserved():
    x = np.arange(12).reshape(6, 2)
    y = np.arange(6)
    batches = list(BatchIterator((x, y), 3))
    assert isinstance(batches[0], tuple) and len(batches[0]) == 2
    np.testing.assert_array_equal(batches[0][1], [0, 1, 2])


# -------------------------------------------------- parquet streaming --

@pytest.fixture
def parquet_store(tmp_path):
    pytest.importorskip("pyarrow")
    from horovod_tpu.cluster.parquet_store import ParquetStore

    store = ParquetStore(str(tmp_path / "store"), rows_per_row_group=8)
    rows = 40
    store.materialize({"x": np.arange(rows * 2, dtype=np.float32)
                            .reshape(rows, 2),
                       "y": np.arange(rows, dtype=np.int64)})
    return store


def test_parquet_stream_matches_read_shard(parquet_store):
    for rank in (0, 1):
        streamed = np.concatenate(
            [b["y"] for b in ParquetShardIterator(
                parquet_store, rank, 2, batch_size=4)])
        direct = parquet_store.read_shard(rank, 2,
                                          trim_to_min=False)["y"]
        np.testing.assert_array_equal(streamed, direct)


def test_parquet_batches_cross_row_group_boundaries(parquet_store):
    # row groups hold 8 rows; batch_size=5 forces carry-over
    batches = list(ParquetShardIterator(parquet_store, 0, 2,
                                        batch_size=5,
                                        drop_remainder=False))
    # shard 0 holds row groups 0/2/4 = 24 rows; batch 5 crosses the
    # 8-row group boundaries and the 4-row tail is kept
    assert [len(b["y"]) for b in batches] == [5, 5, 5, 5, 4]
    got = np.concatenate([b["y"] for b in batches])
    want = parquet_store.read_shard(0, 2, trim_to_min=False)["y"]
    np.testing.assert_array_equal(got, want)


def test_parquet_shards_disjoint_and_cover(parquet_store):
    seen = [np.concatenate([b["y"] for b in ParquetShardIterator(
        parquet_store, r, 2, batch_size=4)]) for r in (0, 1)]
    assert not set(seen[0]) & set(seen[1])
    np.testing.assert_array_equal(
        np.sort(np.concatenate(seen)), np.arange(40))


def test_parquet_shuffle_covers_all_rows(parquet_store):
    it = ParquetShardIterator(parquet_store, 0, 2, batch_size=4,
                              shuffle=True, seed=3, epochs=2)
    ys = [b["y"] for b in it]
    per_epoch = len(ys) // 2
    want = np.sort(parquet_store.read_shard(0, 2,
                                            trim_to_min=False)["y"])
    for ep in range(2):
        got = np.sort(np.concatenate(
            ys[ep * per_epoch:(ep + 1) * per_epoch]))
        np.testing.assert_array_equal(got, want)
    # rerun with the same seed is identical
    again = [b["y"] for b in ParquetShardIterator(
        parquet_store, 0, 2, batch_size=4, shuffle=True, seed=3,
        epochs=2)]
    for a, b in zip(ys, again):
        np.testing.assert_array_equal(a, b)


def test_parquet_empty_shard_raises(parquet_store):
    with pytest.raises(ValueError, match="no row groups"):
        ParquetShardIterator(parquet_store, 9, 10, batch_size=2)


# ------------------------------------------------------ device prefetch --

def test_prefetch_values_match_and_are_device_resident():
    import jax

    src = BatchIterator(_shard(16), 4)
    host = list(BatchIterator(_shard(16), 4))
    dev = list(prefetch_to_device(iter(src), size=2))
    assert len(dev) == len(host)
    for h, d in zip(host, dev):
        assert isinstance(d["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(d["x"]), h["x"])
        np.testing.assert_array_equal(np.asarray(d["y"]), h["y"])


def test_prefetch_with_spmd_sharding():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 8})
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    batches = list(prefetch_to_device(
        iter(BatchIterator(_shard(32), 16)), sharding=sharding))
    assert len(batches) == 2
    for b in batches:
        assert b["x"].sharding == sharding
        # 16 rows over 8 devices -> 2-row shards
        assert b["x"].addressable_shards[0].data.shape == (2, 3)


def test_prefetch_mesh_builds_global_batch():
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"hvd": 8})
    batches = list(prefetch_to_device(
        iter(BatchIterator(_shard(16), 8)), mesh=mesh))
    # single-process: local rows ARE the global batch, sharded over hvd
    assert batches[0]["x"].shape == (8, 3)
    assert len(batches[0]["x"].addressable_shards) == 8


def test_prefetch_propagates_source_errors():
    def bad():
        yield {"x": np.zeros((2, 2)), "y": np.zeros(2)}
        raise RuntimeError("loader died")

    it = prefetch_to_device(bad(), size=1)
    next(it)
    with pytest.raises(RuntimeError, match="loader died"):
        next(it)


def test_prefetch_early_close_releases_producer():
    import time

    produced = []

    def src():
        for i in range(100):
            produced.append(i)
            yield {"x": np.full((2, 2), i)}

    it = prefetch_to_device(src(), size=1)
    next(it)
    it.close()  # training loop exits early
    time.sleep(0.5)  # producer must stop, not fill forever
    n = len(produced)
    time.sleep(0.3)
    assert len(produced) == n, "producer kept running after close"
    assert n < 100


def test_parquet_shard_smaller_than_batch_raises(parquet_store):
    # shard 0 of 2 holds 24 rows; batch 64 would yield zero batches
    with pytest.raises(ValueError, match="every epoch would be empty"):
        ParquetShardIterator(parquet_store, 0, 2, batch_size=64)


def test_parquet_stream_bf16_to_device(tmp_path):
    """bf16 columns stream through the pipeline and land on device as
    bf16 jax.Arrays (the TPU training dtype)."""
    pytest.importorskip("pyarrow")
    import ml_dtypes
    import jax.numpy as jnp

    from horovod_tpu.cluster.parquet_store import ParquetStore

    store = ParquetStore(str(tmp_path / "bf16"), rows_per_row_group=8)
    x = np.arange(64, dtype=np.float32).astype(
        ml_dtypes.bfloat16).reshape(32, 2)
    store.materialize({"x": x})
    batches = list(prefetch_to_device(
        iter(ParquetShardIterator(store, 0, 1, batch_size=8))))
    assert len(batches) == 4
    assert batches[0]["x"].dtype == jnp.bfloat16
    got = np.concatenate([np.asarray(b["x"].astype(jnp.float32))
                          for b in batches])
    np.testing.assert_array_equal(got, x.astype(np.float32))


def test_prefetch_rejects_bad_args():
    with pytest.raises(ValueError, match="size"):
        prefetch_to_device(iter([]), size=0)
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 8})
    with pytest.raises(ValueError, match="not both"):
        prefetch_to_device(iter([]), sharding=object(), mesh=mesh)


def test_prefetch_close_releases_all_staged_batches(monkeypatch):
    """Early close must promptly release EVERY device-staged batch —
    including one a producer mid-``q.put`` lands after the first drain
    pass (the round-5 shutdown race): no batch may stay pinned in the
    queue waiting for garbage collection."""
    import time
    import weakref

    import jax

    refs = []
    real_put = jax.device_put

    def tracking_put(x):
        out = real_put(x)
        refs.append(weakref.ref(out))
        return out

    monkeypatch.setattr(jax, "device_put", tracking_put)

    def src():
        for i in range(10):
            yield np.full((4,), i, np.float32)

    it = prefetch_to_device(src(), size=2)
    first = next(it)
    it.close()
    del first
    # keep `it` alive: the leak mode was "pinned in the queue until the
    # GENERATOR is collected" — releasing must not depend on that
    deadline = time.time() + 3.0
    while any(r() is not None for r in refs) and time.time() < deadline:
        time.sleep(0.05)
    alive = sum(r() is not None for r in refs)
    assert alive == 0, f"{alive} staged device batches still pinned"
    assert it is not None


# ------------------------- what the path records about itself
# (horovod_tpu/utils/trace.py: the hvd.data.* spans and the batch log)
@pytest.fixture
def batch_log():
    from horovod_tpu.utils import trace

    trace.reset()
    yield trace.BATCHES
    trace.reset()


def _small_batches(n, sleep=0.0):
    import time

    for i in range(n):
        time.sleep(sleep)
        yield {"x": np.full((4, 2), i, np.float32),
               "y": np.full((4,), i, np.int32)}


def test_prefetch_spans_join_by_batch_id_across_two_threads(
        batch_log, tmp_path):
    """Under a profiler trace the three spans are in the profiler's own
    file, on ``/host:CPU``: ``hvd.data.next`` and ``hvd.data.put`` on
    the producer's line, ``hvd.data.wait`` on the caller's, and the
    spans of one batch carry one id."""
    import glob
    import os

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        taken = list(prefetch_to_device(_small_batches(4)))
    finally:
        jax.profiler.stop_trace()
    assert len(taken) == 4
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    lines = [
        [(e.name, dict(e.stats).get("batch"), e.start_ns,
          e.start_ns + e.duration_ns)
         for e in line.events if e.name.startswith("hvd.data.")]
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for line in plane.lines]
    (caller,) = [line for line in lines
                 if any(name == "hvd.data.wait" for name, *_ in line)]
    (producer,) = [line for line in lines if line and line is not caller]
    assert {name for name, *_ in caller} == {"hvd.data.wait"}
    assert {name for name, *_ in producer} == {"hvd.data.next",
                                                "hvd.data.put"}
    ids = [record[0] for record in batch_log]
    assert len(ids) == 4 and ids == sorted(set(ids))
    by_name = {name: {batch: (start, end) for n, batch, start, end in line
                      if n == name and batch is not None}
               for line in (caller, producer) for name, *_ in line}
    for batch in ids:
        source, put, wait = (by_name[name][batch] for name in (
            "hvd.data.next", "hvd.data.put", "hvd.data.wait"))
        # made, moved, then handed over; the wait may have begun early
        assert source[1] <= put[0] and put[1] <= wait[1]
    # the asks that ended the epoch: a source call and a wait, no batch
    assert len(caller) == 5 and len(producer) == 4 * 2 + 1
    assert [batch for _, batch, *_ in caller].count(None) == 1


def test_prefetch_log_is_ordered_and_every_stamp_monotone(batch_log):
    taken = list(prefetch_to_device(_small_batches(6), size=2))
    assert len(taken) == len(batch_log) == 6
    ids = [record[0] for record in batch_log]
    assert ids == sorted(set(ids))
    for record in batch_log:
        assert len(record) == 10
        assert record[1] == 4 * 2 * 4 + 4 * 4  # the batch's bytes
        t_next_start, t_host_ready, t_put_end, t_asked, t_taken = record[2:7]
        assert 0 < t_next_start <= t_host_ready <= t_put_end <= t_taken
        assert t_asked <= t_taken
        assert 0 <= record[7] <= 2 and isinstance(record[8], bool)
        assert record[9] is False  # a generator hands over its own arrays
    # one producer: a batch is made after the one before it was staged
    for before, after in zip(batch_log, list(batch_log)[1:]):
        assert before[4] <= after[2] and before[6] <= after[5]


def test_a_slow_source_reads_as_a_starved_loop(batch_log):
    """The loop asks, finds nothing staged and waits about as long as
    the source takes for one batch."""
    import horovod_tpu as hvd

    list(prefetch_to_device(_small_batches(5, sleep=0.05)))
    assert [record[7] for record in batch_log] == [0] * 5
    for record in batch_log:
        assert 0.045e9 <= record[3] - record[2]   # the source's work
        assert 0.03e9 <= record[6] - record[5] <= 0.5e9  # the loop's wait
    stats = hvd.input_stats()
    assert stats["batches"] == 5 and stats["starved_share"] == 1.0
    assert stats["source_ms"] >= 45 and stats["wait_ms"] >= 30


def test_a_slow_consumer_finds_the_queue_full_and_does_not_wait(batch_log):
    import time

    import horovod_tpu as hvd

    import jax

    jax.device_put(0)  # the backend is up before the clock matters
    it = prefetch_to_device(_small_batches(8), size=2)
    for _ in range(4):
        time.sleep(0.15)  # the step; the producer refills meanwhile
        next(it)
    it.close()
    assert [record[7] for record in batch_log] == [2] * 4
    assert all(record[8] for record in batch_log)
    assert max(record[6] - record[5] for record in batch_log) < 0.05e9
    assert hvd.input_stats()["starved_share"] == 0.0


def test_a_prefetcher_closed_early_logs_only_the_batches_taken(batch_log):
    import time

    it = prefetch_to_device(_small_batches(100), size=2)
    first = next(it)["y"]
    time.sleep(0.2)  # two more are staged, a third is in the producer's hand
    it.close()
    assert [int(first[0])] == [0]
    assert len(batch_log) == 1
    # the drained batches took ids, and left no record
    following = list(prefetch_to_device(_small_batches(1)))
    assert len(following) == 1 and len(batch_log) == 2
    assert batch_log[1][0] > batch_log[0][0] + 1


def test_a_source_error_reaches_the_loop_after_the_batches_before_it(
        batch_log):
    def source():
        yield from _small_batches(2)
        raise RuntimeError("row group unreadable")

    it = prefetch_to_device(source())
    assert [int(next(it)["y"][0]) for _ in range(2)] == [0, 1]
    with pytest.raises(RuntimeError, match="row group unreadable"):
        next(it)
    assert len(batch_log) == 2


# ------------------------- who owns a host batch (docs/data.md): the
# cursor of a BatchIterator gathers into memory it is handed, and
# prefetch_to_device keeps that memory and hands it out again once the
# device arrays staged from it have landed and share no memory with it
def _pool(structure, rows=22):
    x = np.random.default_rng(5).standard_normal((rows, 3, 2)).astype(
        np.float32)
    y = np.arange(rows, dtype=np.int32)
    return {"dict": {"x": x, "y": y}, "tuple": (x, y), "array": x}[structure]


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(w, np.ndarray) or type(g) is type(w)
        if isinstance(w, dict):
            assert list(g) == list(w)
        for a, b in zip(_arrays(g), _arrays(w)):
            a = np.asarray(a)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)


def _copying_put(x, sharding=None):
    """A ``device_put`` that never hands the host memory back as the
    device array (the CPU client does, for memory aligned to 64 bytes):
    what a chip's runtime does."""
    import jax
    import jax.numpy as jnp

    out = jnp.array(x, copy=True)
    return out if sharding is None else jax.device_put(out, sharding)


def _aligned_like(batch, align=64):
    """Arrays of ``batch``'s shapes whose memory the CPU client hands
    back as the device array, uncopied (it does for 64 bytes)."""
    def one(a):
        raw = np.empty(a.nbytes + align, np.uint8)
        start = -raw.ctypes.data % align
        return raw[start:start + a.nbytes].view(a.dtype).reshape(a.shape)

    return _map(one, batch)


class _Spy:
    """A cursor that notes the host addresses ``take_into`` is handed
    (``None`` where it is asked to make a new set, which is then made
    like ``aligned``, a full batch, where one is given)."""

    def __init__(self, cursor, aligned=None):
        self._cursor, self._aligned = cursor, aligned
        self.handed = []

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._cursor)

    def take_into(self, out=None):
        self.handed.append(None if out is None else tuple(
            a.ctypes.data for a in _arrays(out)))
        if out is None and self._aligned is not None:
            out = _aligned_like(self._aligned)
        return self._cursor.take_into(out)


@pytest.mark.parametrize("backend", ["as_it_is", "copies", "aliases"])
@pytest.mark.parametrize("structure", ["dict", "tuple", "array"])
def test_prefetch_over_a_cursor_gives_the_iterators_own_batches(
        structure, backend, batch_log, monkeypatch):
    """Two shuffled epochs with a short last batch, whatever the
    backend does with the host memory (a copy, as a chip's runtime; the
    memory itself, as the CPU client for aligned arrays; this
    sandbox's mix of both): compared only after EVERY batch is taken,
    so that a set filled again too early shows."""
    import jax

    if backend == "copies":
        monkeypatch.setattr(jax, "device_put", _copying_put)

    def batches():
        return BatchIterator(_pool(structure), 4, shuffle=True, seed=11,
                             drop_remainder=False, epochs=2)

    want = list(batches())
    assert [len(_arrays(b)[0]) for b in want] == [4, 4, 4, 4, 4, 2] * 2
    source = _Spy(iter(batches()),
                  aligned=want[0] if backend == "aliases" else None)
    got = list(prefetch_to_device(source, size=2))
    _assert_same_batches(got, want)
    assert len(batch_log) == 12
    reused = [record[9] for record in batch_log]
    assert reused == [handed is not None for handed in source.handed[:12]]
    if backend == "copies":
        assert sum(reused) >= 12 - 4
    if backend == "aliases":
        assert not any(reused)
    # the iterator itself is a source too, not only its cursor
    _assert_same_batches(list(prefetch_to_device(batches(), size=1)), want)


def test_take_into_fills_the_arrays_it_is_handed_bit_for_bit():
    data = _pool("dict")
    fresh = list(BatchIterator(data, 4, shuffle=True, seed=3,
                               drop_remainder=False))
    cursor = iter(BatchIterator(data, 4, shuffle=True, seed=3,
                                drop_remainder=False))
    batch, kept = cursor.take_into()
    assert {k: (v.shape, v.dtype) for k, v in kept.items()} == {
        "x": ((4, 3, 2), np.float32), "y": ((4,), np.int32)}
    where = {k: v.ctypes.data for k, v in kept.items()}
    taken = [{k: v.copy() for k, v in batch.items()}]
    for _ in fresh[1:]:
        batch, again = cursor.take_into(kept)
        assert again is kept
        for k, v in batch.items():  # the set's own leading rows
            assert v.ctypes.data == where[k] and v.base is kept[k]
        taken.append({k: v.copy() for k, v in batch.items()})
    _assert_same_batches(taken, fresh)
    assert len(taken[-1]["y"]) == 2  # the short one, in a full-size set
    with pytest.raises(StopIteration):
        cursor.take_into(kept)
    with pytest.raises(StopIteration):
        next(cursor)


def test_plain_next_of_the_cursor_still_yields_arrays_of_its_own():
    data = _pool("dict")
    cursor = iter(BatchIterator(data, 4, epochs=None))
    assert iter(cursor) is cursor
    taken = [next(cursor) for _ in range(8)]
    arrays = [a for batch in taken for a in _arrays(batch)]
    for i, a in enumerate(arrays):
        assert a.flags.owndata
        assert not any(np.shares_memory(a, b) for b in arrays[i + 1:])
        assert not any(np.shares_memory(a, d) for d in data.values())
    # ... and a batch taken into a set does not disturb them
    before = [a.copy() for a in arrays]
    cursor.take_into()
    for a, b in zip(arrays, before):
        np.testing.assert_array_equal(a, b)


def test_a_warm_ring_allocates_nothing_of_a_batchs_size(batch_log,
                                                        monkeypatch):
    """After the first batches the producer gathers into host memory it
    has used before: the same addresses come round again and no array of
    the batch's size is allocated, whichever thread one looks at."""
    import time
    import tracemalloc

    import jax

    put_from = []

    def put(x, sharding=None):
        put_from.append(x.ctypes.data)
        # landed when the call returns: the set is free for the next
        # gather, so that one set serves, however the threads are timed
        return _copying_put(x).block_until_ready()

    monkeypatch.setattr(jax, "device_put", put)
    pool = np.random.default_rng(0).standard_normal((64, 64, 256)).astype(
        np.float32)
    batch_bytes = 8 * 64 * 256 * 4  # 512 KiB
    spy = _Spy(iter(BatchIterator(pool, 8, shuffle=True, epochs=None)))
    it = prefetch_to_device(spy, size=2)
    got = [next(it) for _ in range(8)]  # warm: every set has been made
    time.sleep(0.2)  # the producer is parked on a full queue
    made = spy.handed.count(None)
    assert made == 1
    tracemalloc.start()
    try:
        got += [next(it) for _ in range(24)]
        time.sleep(0.2)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    it.close()
    assert peak < batch_bytes, peak
    assert spy.handed.count(None) == made  # no further set
    assert len(set(put_from)) == made and len(put_from) >= 32
    assert [record[9] for record in batch_log].count(False) == made
    want = iter(BatchIterator(pool, 8, shuffle=True, epochs=None))
    _assert_same_batches(got, [next(want) for _ in got])


class _Unready:
    """A device array whose copy lands when the test says so."""

    def __init__(self, array, landed):
        self._array, self._landed = array, landed
        self.nbytes = array.nbytes

    def is_ready(self):
        return self._landed.is_set()

    def block_until_ready(self):
        assert self._landed.wait(30)
        return self

    @property
    def addressable_shards(self):
        return self._array.addressable_shards

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._array)


def test_a_set_is_not_filled_again_before_its_copy_has_landed(
        batch_log, monkeypatch):
    """``device_put`` returns before the copy ends and the runtime reads
    the host memory until then: the prefetcher makes ``size + 2`` sets
    and no more, waits for the OLDEST copy, and only then gathers into
    that set again (inside the batch's ``next`` stamps)."""
    import threading
    import time

    import jax

    landed, host_views, all_land = [], [], []

    def put(x, sharding=None):
        landed.append(threading.Event())
        if all_land:
            landed[-1].set()
        host_views.append(x)  # what the runtime would still be reading
        return _Unready(_copying_put(x), landed[-1])

    monkeypatch.setattr(jax, "device_put", put)
    pool = np.arange(40 * 6, dtype=np.float32).reshape(40, 6)
    want = list(BatchIterator(pool, 4))
    spy = _Spy(iter(BatchIterator(pool, 4)))
    it = prefetch_to_device(spy, size=2)
    def until(done):
        deadline = time.monotonic() + 10
        while not done() and time.monotonic() < deadline:
            time.sleep(0.01)

    got = [next(it) for _ in range(2)]
    until(lambda: len(host_views) == 4)  # the fourth batch is being put
    time.sleep(0.3)  # a fifth gather would have begun by now
    assert spy.handed == [None] * 4  # size + 2 sets, every copy under way
    for view, batch in zip(host_views, want):
        np.testing.assert_array_equal(view, batch)  # none overwritten
    landed[1].set()  # not the oldest: the producer waits for that one
    time.sleep(0.2)
    assert len(spy.handed) == 4
    waited_from = time.perf_counter_ns()
    landed[0].set()
    got += [next(it) for _ in range(3)]  # 5 taken: the fifth was made
    until(lambda: len(spy.handed) == 6)
    # the two sets whose copies have landed, and no new one
    assert set(spy.handed[4:6]) == {(view.ctypes.data,)
                                    for view in host_views[:2]}
    all_land.append(True)
    for event in landed:
        event.set()
    got += list(it)
    _assert_same_batches(got, want)
    # the wait lies between t_next_start and t_host_ready of batch 5
    fifth = batch_log[4]
    assert fifth[2] < waited_from < fifth[3] and fifth[9] is True
    assert [record[9] for record in batch_log][:4] == [False] * 4


def test_a_set_the_device_array_aliases_is_never_handed_out_again(
        batch_log):
    """The CPU client hands memory aligned to 64 bytes back as the
    device array, uncopied: the batch the loop holds IS that set."""
    pool = np.arange(40 * 6, dtype=np.float32).reshape(40, 6)
    spy = _Spy(iter(BatchIterator(pool, 4)), aligned=pool[:4])
    got = list(prefetch_to_device(spy, size=2))
    assert spy.handed == [None] * 11  # ten batches and the end's ask
    where = {a.addressable_shards[0].data.unsafe_buffer_pointer()
             for a in got}
    assert len(where) == 10 and all(at % 64 == 0 for at in where)
    _assert_same_batches(got, list(BatchIterator(pool, 4)))
    assert [record[9] for record in batch_log] == [False] * 10


def test_sources_that_fill_no_memory_they_are_given_read_reused_false(
        batch_log, parquet_store):
    taken = list(prefetch_to_device(_small_batches(3)))
    taken += list(prefetch_to_device(iter(ParquetShardIterator(
        parquet_store, 0, 2, batch_size=4))))
    assert len(taken) == len(batch_log) == 3 + 6
    assert [record[9] for record in batch_log] == [False] * 9


@pytest.mark.parametrize("placement", ["sharding", "mesh"])
def test_the_ring_holds_or_stands_aside_under_either_placement(
        placement, batch_log):
    """Eight CPU devices: the shards of a staged array may lie inside
    the host set (aligned rows) or not, and the arrays say which."""
    from jax.sharding import NamedSharding, PartitionSpec

    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"hvd": 8})
    where = ({"mesh": mesh} if placement == "mesh" else
             {"sharding": NamedSharding(mesh, PartitionSpec("hvd"))})
    pool = _pool("dict", rows=64)
    want = list(BatchIterator(pool, 16, shuffle=True, seed=2, epochs=3))
    got = list(prefetch_to_device(
        iter(BatchIterator(pool, 16, shuffle=True, seed=2, epochs=3)),
        **where))
    assert len(got[0]["x"].addressable_shards) == 8
    _assert_same_batches(got, want)


def test_early_close_releases_the_producer_with_a_ring_in_hand(
        monkeypatch):
    import threading
    import time
    import weakref

    import jax

    refs = []

    def put(x, sharding=None):
        out = _copying_put(x)
        refs.append(weakref.ref(out))
        return out

    monkeypatch.setattr(jax, "device_put", put)
    before = set(threading.enumerate())
    spy = _Spy(iter(BatchIterator(_pool("array", rows=64), 4, epochs=None)))
    it = prefetch_to_device(spy, size=2)
    first = [next(it) for _ in range(6)]
    (producer,) = set(threading.enumerate()) - before
    it.close()
    del first
    producer.join(timeout=3)
    assert not producer.is_alive()
    n = len(spy.handed)
    assert any(handed is not None for handed in spy.handed)  # a ring
    deadline = time.time() + 3.0
    while any(r() is not None for r in refs) and time.time() < deadline:
        time.sleep(0.05)
    assert not any(r() is not None for r in refs)
    assert len(spy.handed) == n
