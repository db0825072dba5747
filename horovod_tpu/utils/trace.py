"""The eager plane's request lifecycle, recorded where the work happens.

Two instruments, both always on (docs/observability.md):

- **Spans on the profiler's clock.**  ``with trace.span("hvd.launch"):``
  is ``jax.profiler.TraceAnnotation`` itself.  Outside a ``jax.profiler``
  trace it checks one flag and records nothing; inside one the span
  lands in the profiler's own file, on ``/host:CPU``, on the clock the
  device planes use, so the plane's host work can be laid beside the
  chip's.  By thread:

      caller      hvd.submit  hvd.wait
      dispatcher  hvd.wait_batch  hvd.decode  hvd.mark_done
                  hvd.execute > hvd.exec.{assemble, lookup, launch,
                                          complete}

  An allreduce response is those four under its ``hvd.execute``:
  ``assemble`` hands the ranks' own tensors to the collective program
  without a device program, ``launch`` is the response's one launch.
  The other collectives stage a buffer first: ``hvd.exec.fuse_in``
  (reduce_scatter, broadcast, adasum: a small jitted program a rank) and
  ``hvd.exec.stack`` (those and allgather, alltoall) in ``assemble``'s
  place.

- **A request log.**  One tuple per finished request, in
  ``time.perf_counter_ns()``:

      (request id, response id,
       t_submit, t_enqueued, t_execute_start, t_done)

  The stamps ride on the request's :class:`~horovod_tpu.common.handles.
  Handle` from ``ops/eager.py:_submit`` to ``Handle.set_result``, under
  the native and the Python controller alike.  The requests of one
  fused response share its response id.  The log is the module's, so it
  outlives ``hvd.shutdown()`` (a benchmark reads it after closing its
  loop); ``hvd.init()`` and :func:`reset` empty it.
"""

import collections
import itertools
import time

import jax

span = jax.profiler.TraceAnnotation
now = time.perf_counter_ns

LOG = collections.deque(maxlen=65536)
_request_ids = itertools.count(1)
_response_ids = itertools.count(1)


def submitted(handle, t_submit):
    """``_submit`` made ``handle`` at ``t_submit``: its request id."""
    handle.request_id = next(_request_ids)
    handle.t_submit = t_submit


def executing(entries, start):
    """The dispatcher took up one response at ``start``: every request
    in it gets the response's id and that time."""
    response_id = next(_response_ids)
    for entry in entries:
        for handle in entry.handles.values():
            handle.response_id = response_id
            handle.t_execute_start = start


def finished(handle):
    """``handle`` has its result: the request's line of the log.  The
    caller's ``t_enqueued`` stamp races the dispatcher, which may take
    the request up before the caller is back from ``enqueue``; such a
    request waited for nobody, so its stamp is held to the start of its
    execution."""
    start = handle.t_execute_start
    LOG.append((handle.request_id, handle.response_id, handle.t_submit,
                min(handle.t_enqueued or start, start), start, now()))


def reset():
    LOG.clear()


def eager_stats():
    """Counts and mean microseconds per stage over the log (the last
    65,536 requests since ``hvd.init()``): what one request cost its
    caller (``submit_us``), how long it waited for the dispatcher
    (``queue_wait_us``), and what one response cost the dispatcher
    (``execute_us``).  Beside ``controller.cache_stats()``."""
    records = list(LOG)
    responses = {}
    for _, response_id, _, _, start, done in records:
        responses[response_id] = max(responses.get(response_id, 0),
                                     done - start)
    stats = {"requests": len(records), "responses": len(responses)}
    if records:
        n = len(records)
        stats.update(
            submit_us=sum(r[3] - r[2] for r in records) / n / 1e3,
            queue_wait_us=sum(r[4] - r[3] for r in records) / n / 1e3,
            execute_us=sum(responses.values()) / len(responses) / 1e3)
    return stats
