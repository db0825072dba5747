"""TPU-native input pipeline: sharded batch iteration + device prefetch.

The reference's data path is framework loaders feeding each rank its own
shard — torch ``DataLoader`` + ``DistributedSampler`` in the examples,
and Petastorm readers over per-rank Parquet row groups in the estimators
(``horovod/spark/keras/remote.py``: ``cur_shard=hvd.rank(),
shard_count=hvd.size()``).  The TPU equivalent below keeps the same
contract (disjoint per-rank shards, deterministic per-epoch shuffling)
and adds the piece TPU training actually needs: **device prefetch**.
An XLA training step dispatches asynchronously; if the NEXT batch's
host→device transfer only starts when the step returns, the HBM copy
sits on the critical path.  ``prefetch_to_device`` overlaps the copy
with compute via a background thread and a bounded queue, handing the
step loop batches that are already device-resident ``jax.Array``s.

**Who owns a host batch** (docs/data.md).  A batch a caller takes with
``next()`` is the caller's: fresh arrays, as ever.  A source that can
gather into memory it is handed says so by a ``take_into`` method (the
cursor ``iter(BatchIterator(...))`` returns has one), and then
``prefetch_to_device`` owns the host memory: a few sets of arrays of the
batch's shapes that it hands the source again and again, each only once
every device array staged from it has landed (``is_ready()``:
``device_put`` returns before the copy ends) and shares no memory with
it.  A fresh array of a batch's size is mapped anew and its pages are
faulted in one by one, which on some hosts costs ten times the copy.

Pieces:

- :class:`BatchIterator` — batches over in-memory arrays (the
  ``read_shard`` output), per-epoch seeded reshuffle; its cursor can
  gather into arrays the caller keeps.
- :class:`ParquetShardIterator` — streams THIS rank's Parquet row groups
  (``rg % shard_count == cur_shard``) one group at a time, so the shard
  never has to fit in host memory at once.
- :func:`prefetch_to_device` — background host→device staging; accepts a
  ``jax.sharding.Sharding`` for SPMD global batches or a ``Mesh`` (uses
  :func:`horovod_tpu.parallel.mesh.shard_global_batch` per batch).
"""

import queue
import threading

import numpy as np

__all__ = ["BatchIterator", "ParquetShardIterator", "prefetch_to_device",
           "lockstep_plan", "lockstep_shard_batches", "min_shard_rows",
           "require_sharded_store"]


def _leaves(tree):
    """The arrays of a {name: array} dict / tuple / array."""
    if isinstance(tree, dict):
        return list(tree.values())
    return list(tree) if isinstance(tree, (tuple, list)) else [tree]


def _map(fn, data, *rest):
    """``fn`` over the arrays of ``data`` (and, beside each, those of
    ``rest``: structures of the same kind), in ``data``'s structure."""
    if isinstance(data, dict):
        return {k: fn(v, *(r[k] for r in rest)) for k, v in data.items()}
    if isinstance(data, (tuple, list)):
        return type(data)(fn(*vs) for vs in zip(data, *rest))
    return fn(data, *rest)


def _tree_rows(data):
    """Leading-dim length of a batch structure."""
    arrays = _leaves(data)
    if not arrays:
        raise ValueError("empty batch structure")
    rows = {int(np.shape(a)[0]) for a in arrays}
    if len(rows) != 1:
        raise ValueError(f"ragged leading dims: {sorted(rows)}")
    return rows.pop()


def _tree_take(data, idx):
    return _map(lambda v: v[idx], data)


class BatchIterator:
    """Deterministic batcher over in-memory per-rank shard data.

    ``data``: ``{name: array}`` dict (the ``ParquetStore.read_shard``
    output), tuple of arrays, or one array — batches keep the structure.
    ``shuffle``: reshuffles every epoch with ``seed + epoch`` so runs are
    reproducible and ranks (which hold disjoint shards) need no
    coordination — the reference gets the same property from
    ``DistributedSampler.set_epoch``.
    ``epochs=None`` iterates forever (the training-loop default: the
    step count, not the iterator, ends training).
    """

    def __init__(self, data, batch_size, *, shuffle=False, seed=0,
                 drop_remainder=True, epochs=1):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be > 0, got {batch_size}")
        self._data = data
        self._rows = _tree_rows(data)
        if self._rows == 0:
            raise ValueError("shard has zero rows")
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.epochs = epochs
        if drop_remainder and self._rows < batch_size:
            raise ValueError(
                f"shard rows ({self._rows}) < batch_size ({batch_size}) "
                f"with drop_remainder — every epoch would be empty")

    @property
    def batches_per_epoch(self):
        if self.drop_remainder:
            return self._rows // self.batch_size
        return -(-self._rows // self.batch_size)

    def _batch_rows(self):
        """The rows of each batch in turn, as index arrays."""
        epoch = 0
        while self.epochs is None or epoch < self.epochs:
            if self.shuffle:
                order = np.random.default_rng(
                    self.seed + epoch).permutation(self._rows)
            else:
                order = np.arange(self._rows)
            stop = (self._rows - self._rows % self.batch_size
                    if self.drop_remainder else self._rows)
            for lo in range(0, stop, self.batch_size):
                yield order[lo:lo + self.batch_size]
            epoch += 1

    def __iter__(self):
        return _BatchCursor(self._data, self.batch_size, self._batch_rows())


class _BatchCursor:
    """One pass over a :class:`BatchIterator`'s batches.

    An ordinary iterator: ``next()`` gives a batch of fresh arrays, the
    caller's to keep.  :meth:`take_into` gives the same batch (same
    order, same rows, same dtype) in arrays the caller keeps and passes
    again, for a loop that is done with a batch before it takes the
    next but one (``prefetch_to_device``, once the batch is on the
    device): a fresh array of a batch's size is mapped and unmapped by
    the allocator on every batch and its pages faulted in one by one.
    """

    def __init__(self, data, batch_size, batch_rows):
        self._data = data
        self._batch_size = batch_size
        self._batch_rows = batch_rows

    def __iter__(self):
        return self

    def __next__(self):
        return _tree_take(self._data, next(self._batch_rows))

    def take_into(self, out=None):
        """Gather the next batch into ``out`` and return ``(batch,
        out)``; ``StopIteration`` where ``next()`` would raise it.

        ``out``: arrays of a FULL batch's shapes, leaf for leaf in the
        batch's structure, as an earlier call returned them; ``None``
        makes a new set.  ``batch`` is views of ``out``'s leading rows:
        all of them, but for an epoch's short last batch.  Whatever
        ``out`` held is overwritten: pass a set again only when nothing
        reads the batch taken into it any more.
        """
        idx = next(self._batch_rows)
        if out is None:
            out = _map(lambda v: np.empty(
                (self._batch_size,) + np.shape(v)[1:], v.dtype), self._data)

        def take(v, o):
            o = o[:len(idx)]
            # mode="clip" writes straight into ``o``; the default
            # ("raise") gathers into a fresh array of the batch's size
            # first.  ``idx`` is a permutation's: nothing to clip
            np.take(v, idx, axis=0, out=o, mode="clip")
            return o

        return _map(take, self._data, out), out


class ParquetShardIterator:
    """Stream this rank's Parquet row groups into batches, one group in
    memory at a time.

    Matches ``ParquetStore.read_shard`` semantics (disjoint row groups
    ``rg % shard_count == cur_shard``, reference Petastorm wiring in
    ``horovod/spark/keras/remote.py``) without materializing the whole
    shard: rows left over when a group is exhausted carry into the next
    group's batches, so batch boundaries don't leak the row-group size.
    ``shuffle`` permutes the rank's row-group ORDER per epoch and the
    rows inside each group (window shuffle — the memory bound is one
    row group, same trade-off as Petastorm's shuffling buffer).
    """

    def __init__(self, store, cur_shard, shard_count, batch_size, *,
                 split="train", idx=None, columns=None, shuffle=False,
                 seed=0, drop_remainder=True, epochs=1):
        if not 0 <= cur_shard < shard_count:
            raise ValueError(
                f"cur_shard {cur_shard} outside [0, {shard_count})")
        if batch_size <= 0:
            raise ValueError(f"batch_size must be > 0, got {batch_size}")
        self._store = store
        self._cur_shard = cur_shard
        self._shard_count = shard_count
        self._split = split
        self._idx = idx
        self._columns = columns
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.epochs = epochs
        pf = store._open(split, idx)
        self._groups = [rg for rg in range(pf.metadata.num_row_groups)
                        if rg % shard_count == cur_shard]
        if not self._groups:
            raise ValueError(
                f"shard {cur_shard}/{shard_count} holds no row groups "
                f"({pf.metadata.num_row_groups} total) — rewrite with "
                f"smaller rows_per_row_group or fewer ranks")
        rows = sum(pf.metadata.row_group(rg).num_rows
                   for rg in self._groups)
        if drop_remainder and rows < batch_size:
            # same check BatchIterator does in __init__: an epoch that
            # yields nothing must fail loudly at construction, not run
            # zero training steps silently
            raise ValueError(
                f"shard {cur_shard}/{shard_count} rows ({rows}) < "
                f"batch_size ({batch_size}) with drop_remainder — "
                f"every epoch would be empty")

    def _read_group(self, pf, rg, schema_meta):
        table = pf.read_row_groups([rg], columns=self._columns)
        return self._store._to_numpy(table, schema_meta, table.num_rows)

    def __iter__(self):
        epoch = 0
        while self.epochs is None or epoch < self.epochs:
            rng = (np.random.default_rng(self.seed + epoch)
                   if self.shuffle else None)
            groups = list(self._groups)
            if rng is not None:
                rng.shuffle(groups)
            pf = self._store._open(self._split, self._idx)
            schema_meta = pf.schema_arrow.metadata
            pending = None  # carry-over rows smaller than batch_size
            for rg in groups:
                chunk = self._read_group(pf, rg, schema_meta)
                if rng is not None:
                    chunk = _tree_take(
                        chunk, rng.permutation(_tree_rows(chunk)))
                if pending is not None:
                    chunk = {k: np.concatenate([pending[k], v])
                             for k, v in chunk.items()}
                rows = _tree_rows(chunk)
                stop = rows - rows % self.batch_size
                for lo in range(0, stop, self.batch_size):
                    yield _tree_take(chunk,
                                     slice(lo, lo + self.batch_size))
                pending = (_tree_take(chunk, slice(stop, rows))
                           if stop < rows else None)
            if pending is not None and not self.drop_remainder:
                yield pending
            epoch += 1


def require_sharded_store(store):
    """Fail fast (before any I/O) when a store has no row-group layout
    to stream."""
    if not hasattr(store, "shard_row_counts"):
        raise ValueError(
            "streaming=True needs a sharded-dataset store "
            "(ParquetStore/FilesystemStore); this store has no "
            "row-group layout to stream")


def min_shard_rows(store, num_ranks):
    """Smallest shard's row count (footer metadata only), with the same
    clear empty-shard error ``read_shard`` raises — streaming must not
    degrade it to a ZeroDivisionError downstream."""
    counts = store.shard_row_counts(num_ranks)
    if min(counts) == 0:
        raise ValueError(
            f"shard {counts.index(0)} of {num_ranks} would be empty — "
            f"rewrite with smaller rows_per_row_group or fewer ranks")
    return min(counts)


def lockstep_plan(store, num_ranks, batch_size, epochs):
    """The lockstep trim: (clamped batch_size, steps_per_epoch, total
    steps) derived from the SMALLEST shard, identical on every rank —
    a rank running more per-batch collective rounds than its peers
    hangs the gang.  The streamed analog of ``read_shard``'s
    equal-shard trim; single source of truth for all three estimators'
    streaming paths."""
    rows = min_shard_rows(store, num_ranks)
    batch_size = min(batch_size, rows)
    steps_per_epoch = max(rows // batch_size, 1)
    return batch_size, steps_per_epoch, epochs * steps_per_epoch


def lockstep_shard_batches(store, rank, num_ranks, batch_size, epochs):
    """One rank's streamed batches under the :func:`lockstep_plan` cap
    (JAX and torch eager streaming paths)."""
    import itertools

    batch_size, _, steps = lockstep_plan(store, num_ranks, batch_size,
                                         epochs)
    return itertools.islice(
        iter(ParquetShardIterator(store, rank, num_ranks, batch_size,
                                  epochs=None)), steps)


class _HostSets:
    """The host memory ``prefetch_to_device`` keeps for a source that
    gathers into memory it is handed (``take_into``): sets of arrays of
    the batch's shapes, each handed out again only when every device
    array staged from it has landed and shares no memory with it.

    ``device_put`` may return before the copy ends, and the runtime
    reads the host memory until then; a backend may also hand back an
    array that IS the host memory (the CPU client does, for memory
    aligned to 64 bytes).  Both are asked of the staged arrays
    (``is_ready()``, the shards' addresses), never assumed.  One thread
    uses it, the producer's.
    """

    def __init__(self, limit):
        self._limit = limit  # sets alive at once: free, under way, in hand
        self._free = []
        self._under_way = []  # (set, its device arrays), oldest first

    def free_set(self):
        """A set nothing reads any more, or ``None`` where the source is
        to make a new one.  Waits, for the oldest copy, only where
        ``limit`` sets are all under way."""
        import jax

        self._collect()
        if not self._free and len(self._under_way) >= self._limit:
            jax.block_until_ready(self._under_way[0][1])
            self._collect()
        return self._free.pop() if self._free else None

    def staged(self, held, arrays):
        """``arrays`` are what ``put`` made of the batch in ``held``."""
        self._under_way.append((held, arrays))

    def _collect(self):
        under_way = []
        for held, arrays in self._under_way:
            if not all(array.is_ready() for array in arrays):
                under_way.append((held, arrays))
            elif not _shares_memory(held, arrays):
                self._free.append(held)
            # else: the device arrays ARE this set; it is theirs now
        self._under_way = under_way


def _shares_memory(held, arrays):
    """Whether a shard of a device array lies inside a host array of
    ``held`` (asked once the arrays are ready)."""
    spans = [(a.ctypes.data, a.ctypes.data + a.nbytes)
             for a in _leaves(held)]
    return any(lo <= shard.data.unsafe_buffer_pointer() < hi
               for array in arrays for shard in array.addressable_shards
               for lo, hi in spans)


def prefetch_to_device(iterator, size=2, *, sharding=None, mesh=None,
                       axis=None):
    """Stage batches onto device ahead of the training loop.

    A daemon thread pulls host batches from ``iterator``, moves them to
    device, and parks up to ``size`` device-resident batches in a
    bounded queue — the host→device copy of batch N+1 overlaps the
    compute of batch N instead of serializing after it.  ``size=2`` is
    the classic double buffer; more only helps when batch copy time is
    burstier than step time.

    Placement: default is ``jax.device_put`` to the default device
    (single-chip path); pass ``sharding`` (any ``jax.sharding.Sharding``)
    to lay the batch out for SPMD, or ``mesh`` (+ optional ``axis``) to
    build a multi-host GLOBAL batch from per-process local rows via
    :func:`horovod_tpu.parallel.mesh.shard_global_batch`.

    Host memory: a source with a ``take_into`` method (the cursor of a
    :class:`BatchIterator`; found on ``iter(iterator)``) gathers into
    sets of arrays this prefetcher keeps, at most ``size + 2`` of them
    (one being filled, one whose copy is under way, ``size`` queued),
    each filled again only once the device arrays staged from it have
    landed and share no memory with it (:class:`_HostSets`).  Any other
    iterator's batches are its own fresh arrays, as ever.

    Source-iterator exceptions re-raise at the consuming ``next()`` —
    a data-path failure must fail the step loop, not silently end the
    epoch early.
    """
    import jax

    from horovod_tpu.utils import trace

    if size <= 0:
        raise ValueError(f"size must be > 0, got {size}")
    if sharding is not None and mesh is not None:
        raise ValueError("pass sharding OR mesh, not both")

    if mesh is not None:
        from horovod_tpu.parallel.mesh import MeshAxes, shard_global_batch

        axis = axis or MeshAxes.HVD

        def put(x):
            return shard_global_batch(np.asarray(x), mesh=mesh, axis=axis)
    elif sharding is not None:
        def put(x):
            return jax.device_put(x, sharding)
    else:
        put = jax.device_put

    q = queue.Queue(maxsize=size)
    sentinel = object()
    stop = threading.Event()

    def _put(item):
        # bounded put that gives up when the consumer has stopped — a
        # plain q.put would block this thread forever if the training
        # loop exits early, pinning device batches and the source
        # iterator until process exit
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    sets = _HostSets(size + 2)

    # What the path records about itself (utils/trace.py, always on):
    # three spans on the profiler's clock, and per batch the producer's
    # stamps, which ride through the queue beside the batch and become
    # one record of trace.BATCHES when the consumer takes it.
    def stage(batch_id, batch, held, reused, t_next_start):
        # a frame of its own: nothing of the device batch outlives the
        # _put below in the producer's locals (``sets`` holds its arrays
        # until their copy has landed, where the host set is kept)
        t_host_ready = trace.now()
        with trace.span("hvd.data.put", batch=batch_id):
            staged = jax.tree.map(put, batch)
        if held is not None:
            sets.staged(held, jax.tree.leaves(staged))
        return staged, trace.batch_staged(batch_id, staged, t_next_start,
                                          t_host_ready, reused)

    def producer():
        try:
            source = iter(iterator)
            # where the host memory comes from: a source that can fill
            # memory it is handed gets a kept set (or makes one to keep),
            # any other hands over arrays of its own
            take_into = getattr(source, "take_into", None) or (
                lambda out: (next(source), None))
            while True:
                batch_id = next(trace.batch_ids)
                t_next_start = trace.now()
                with trace.span("hvd.data.next", batch=batch_id):
                    # a wait for a set to come free is part of what the
                    # batch costs before its put.  The host batch lives
                    # until this call returns, as under ``for batch in
                    # iterator``
                    free = sets.free_set()
                    try:
                        batch, held = take_into(free)
                    except StopIteration:
                        break
                if stop.is_set() or not _put(stage(
                        batch_id, batch, held, free is not None,
                        t_next_start)):
                    return
            _put((sentinel, None))
        except BaseException as exc:  # noqa: BLE001 — re-raised consumer-side
            _put((sentinel, exc))

    # start staging NOW (not at first next()): the whole point is the
    # first batch being on device before the loop asks for it
    producer_thread = threading.Thread(target=producer, daemon=True)
    producer_thread.start()

    def consume():
        try:
            while True:
                t_asked = trace.now()
                depth_at_ask = q.qsize()
                with trace.span("hvd.data.wait") as wait:
                    batch, staged = q.get()
                    if batch is not sentinel:
                        wait.set_metadata(batch=staged[0])
                if batch is sentinel:
                    if staged is not None:
                        raise staged
                    return
                trace.batch_taken(staged, t_asked, depth_at_ask)
                yield batch
        finally:
            # consumer done (exhausted, errored, or closed early):
            # release the producer and any queued device batches.  One
            # drain pass is not enough: a producer already inside q.put
            # when stop is set can land one more item after the drain,
            # pinning a device-resident batch until garbage collection.
            # _put re-checks stop before every attempt, so that window
            # closes within one put timeout (0.2s) — keep draining until
            # the producer exits or that window has passed; never block
            # on the SOURCE iterator, which may legally stall.
            import time as _time

            stop.set()
            deadline = _time.monotonic() + 1.0
            while True:
                try:
                    q.get_nowait()
                    continue
                except queue.Empty:
                    pass
                producer_thread.join(timeout=0.05)
                if not producer_thread.is_alive() \
                        or _time.monotonic() > deadline:
                    break
            while True:  # whatever landed during the final join
                try:
                    q.get_nowait()
                except queue.Empty:
                    break

    return consume()
