"""Input pipeline: sharded batch iteration + device prefetch.

Reference analogs: torch DataLoader + DistributedSampler in the
examples (per-epoch seeded reshuffle), Petastorm reader wiring in
``horovod/spark/keras/remote.py`` (per-rank Parquet row groups,
``cur_shard=rank, shard_count=size``)."""

import numpy as np
import pytest

from horovod_tpu.utils.data import (BatchIterator, ParquetShardIterator,
                                    prefetch_to_device)


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
        assert len(record) == 9
        assert record[1] == 4 * 2 * 4 + 4 * 4  # the batch's bytes
        t_next_start, t_host_ready, t_put_end, t_asked, t_taken = record[2:7]
        assert 0 < t_next_start <= t_host_ready <= t_put_end <= t_taken
        assert t_asked <= t_taken
        assert 0 <= record[7] <= 2 and isinstance(record[8], bool)
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
