"""What the program records about itself: the eager plane's request
lifecycle and the input path's batches where the work happens, and the
compiled SPMD step by phase and scope from the names its own
instructions carry.

The eager plane and the input path (``utils/data.py``'s
``prefetch_to_device``) have two instruments each, always on
(docs/observability.md); the SPMD step's is at the end of this module
(:func:`step_phases`):

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
      step loop   hvd.data.wait
      producer    hvd.data.next  hvd.data.put

  An allreduce response is those four under its ``hvd.execute``:
  ``assemble`` hands the ranks' own tensors to the collective program
  without a device program, ``launch`` is the response's one launch.
  The other collectives stage a buffer first: ``hvd.exec.fuse_in``
  (reduce_scatter, broadcast, adasum: a small jitted program a rank) and
  ``hvd.exec.stack`` (those and allgather, alltoall) in ``assemble``'s
  place.  The three of the input path carry their batch's id as the
  annotation's argument ``batch``: ``next`` is the source iterator's
  work for one host batch, ``put`` the batch onto the device, both on
  the prefetcher's thread; ``wait`` is what one ``next()`` on the
  prefetcher cost the loop that trains.

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

- **A batch log**, :data:`BATCHES`, kept like the request log.  One
  tuple per batch a consumer TOOK from a prefetcher, on the same clock,
  appended on the consumer's thread (a batch drained at shutdown leaves
  none):

      (batch id, bytes,
       t_next_start, t_host_ready, t_put_end,   the producer's
       t_asked, t_taken,                        the consumer's
       depth_at_ask, ready_at_take,
       reused)                                  the producer's

  ``depth_at_ask`` is the queue's length when the loop asked (0: nothing
  was staged, the loop waits); ``ready_at_take`` is ``is_ready()`` of
  the batch's largest array when it is handed over (``device_put`` may
  return before the copy ends; nothing blocks to find out); ``reused``,
  the tenth and last, is true when the host batch was gathered into
  memory the prefetcher had kept from an earlier batch, false when the
  source handed over arrays of its own or the kept set was new (its
  pages still to be touched).  Readers take fields by position: a new
  one goes at the end.
"""

import collections
import itertools
import re
import statistics
import time

import jax

span = jax.profiler.TraceAnnotation
now = time.perf_counter_ns

LOG = collections.deque(maxlen=65536)
BATCHES = collections.deque(maxlen=65536)
_request_ids = itertools.count(1)
_response_ids = itertools.count(1)
batch_ids = itertools.count(1)  # one sequence for every prefetcher


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
    BATCHES.clear()


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


def batch_staged(batch_id, batch, t_next_start, t_host_ready, reused):
    """``put`` has returned ``batch``: the producer's half of its
    record, which rides through the prefetcher's queue beside it (with
    the batch's largest array in the last place, for
    :func:`batch_taken` to ask whether the copy has ended)."""
    t_put_end = now()
    leaves = jax.tree.leaves(batch)
    sizes = [leaf.nbytes for leaf in leaves]
    return (batch_id, sum(sizes), t_next_start, t_host_ready, t_put_end,
            reused, leaves[sizes.index(max(sizes))] if leaves else None)


def batch_taken(staged, t_asked, depth_at_ask):
    """The consumer has the batch whose producer's half is ``staged``
    in hand: the batch's line of the log, on the consumer's thread."""
    *stamps, reused, largest = staged
    BATCHES.append((*stamps, t_asked, now(), depth_at_ask,
                    largest is None or largest.is_ready(), reused))


def input_stats():
    """The input path's read-out over the batch log (the last 65,536
    batches taken since ``hvd.init()``): how many and how many bytes,
    the share a loop had to wait for (nothing staged when it asked, or
    the copy still under way when it took), what one ``next()`` cost
    the loop on average (``wait_ms``), and the medians of the source
    iterator's work (``source_ms``) and of the move onto the device
    (``put_ms``) for one batch, and the share whose host batch was
    gathered into memory kept from an earlier one (``reused_share``).
    Beside :func:`eager_stats`."""
    records = list(BATCHES)
    stats = {"batches": len(records),
             "bytes": sum(r[1] for r in records)}
    if records:
        stats.update(
            starved_share=sum(r[7] == 0 or not r[8]
                              for r in records) / len(records),
            wait_ms=sum(r[6] - r[5] for r in records) / len(records) / 1e6,
            source_ms=statistics.median(r[3] - r[2] for r in records) / 1e6,
            put_ms=statistics.median(r[4] - r[3] for r in records) / 1e6,
            reused_share=sum(r[9] for r in records) / len(records))
    return stats


# ----------------------------------------------- the compiled SPMD step
PHASES = ("forward", "recompute", "backward", "exchange", "update",
          "unnamed")
_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
# the computations an instruction runs as its own events; a reducer
# (``to_apply``) never shows as one
_CALLED = re.compile(r"\b(?:calls|body|condition|branch_computations|"
                     r"true_computation|false_computation)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")
# components of a name that say how JAX got there, not where it is
_HOW = re.compile(r"^(while|body|cond|checkpoint|rematted_computation|"
                  r"closed_call|shard_map|pjit|branch_\d+_fun)$")
_MODULE = re.compile(r"^[A-Za-z_]\w*$")
# opcode and operands: the first lower-case word that opens a bracket
# (a layout's ``T(8,128)`` and ``S(1)`` are capitals)
_RAN = re.compile(r"(?:^| )([a-z][\w\-]*)\(([^)]*)\)")


def phase_of(op_name):
    """The phase of an instruction by its ``op_name`` (with its loop's
    ahead of it, see :func:`step_phases`).  In this order:

    1. ``rematted_computation`` in the name: **recompute** (a forward
       pass run again inside the backward pass, ``jax.checkpoint``);
    2. ``transpose(``: **backward**;
    3. ``jvp(``: **forward** (``jax.value_and_grad`` traces the loss
       under ``jvp`` and transposes it: JAX writes both markers itself);
    4. ``hvd/exchange``: **exchange** (the gradient reduction of
       ``DistributedOptimizer``);
    5. any other name with a path (``hvd/update/...``, and what the
       caller's step does around its gradient: ``apply_updates``, the
       ``pmean`` of the loss): **update**;
    6. no name, an argument's label (``params[...]``) or a bare
       primitive (``reduce_sum``): **unnamed** (:func:`step_phases`
       then reads it as its neighbours).
    """
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    if "hvd/exchange" in op_name:
        return "exchange"
    return "update" if "/" in op_name else "unnamed"


def scope_of(op_name):
    """The module path of an instruction below its model's name, the
    copies of a block as one row: the components under the last
    ``jvp(...)`` (the whole name where there is none) without the
    primitive at the end, without what only says how JAX got there
    (``jit(...)``, ``while/body``, ``checkpoint``, ``shard_map``), with
    indices cut (``block_3`` -> ``block``, ``ln1`` -> ``ln``) and a
    doubled name folded (``attn/attn/latent`` -> ``attn/latent``).  What
    ``jvp(...)`` itself names leads the path when it is a scope
    (``jvp(loss)``, ``jvp(mtp)``) and not when it is the model's class
    (``jvp(Transformer)``: a capital).  ``""`` where nothing is left."""
    parts = op_name.split("/")[:-1]
    marked = [i for i, part in enumerate(parts) if "jvp(" in part]
    if marked:
        inner = parts[marked[-1]].rpartition("(")[2].rstrip(")")
        parts = ([inner if inner[:1].islower() else ""]
                 + parts[marked[-1] + 1:])
    out = []
    for part in parts:
        if not _MODULE.match(part) or _HOW.match(part):
            continue
        part = re.sub(r"_?\d+", "", part)
        if part and part != (out[-1] if out else None):
            out.append(part)
    return "/".join(out)


def step_phases(step):
    """A compiled step (``jitted.lower(...).compile()``, or its
    ``as_text()``) by the names its own instructions carry:

        instructions, fused, borrowed = step_phases(compiled)
        instructions["fusion.123"] == ("backward", "block/mlp/up")
        fused["fusion.123"] == {"backward", "update"}

    ``instructions`` holds every instruction of the entry computation
    and of every computation run from there that is not a fusion's
    (loop bodies and conditions, branches, calls): the names a
    profiler's ``XLA Ops`` events carry (docs/observability.md).  Phase
    and scope are :func:`phase_of` and :func:`scope_of` of the
    ``op_name`` in the instruction's metadata (the first, where the
    compiler joined several).

    - An instruction inside a loop is read with its loop's name ahead
      of its own: a Pallas kernel in a scanned block keeps only
      ``.../checkpoint/block_2/ln1`` of its path, and a copy the
      compiler made there has no name at all; both are their loop's.
    - A fusion goes where its own metadata says (the compiler gives it
      the name of its product or root); ``fused`` has, for each fusion,
      the phases of the named instructions inside it, so that a weight
      gradient fused with its Adam update can be shown and not silently
      given to one side.
    - What the compiler made and left unnamed (a weight's prefetch
      ``copy-start`` / ``copy-done``, a layout copy, the kernels it
      lowers ``ragged_dot`` to, which keep ``op_name="ragged-dot-none"``
      alone) is read as the instructions around it: the phase and scope
      of the last of its operands to run (forward before recompute
      before backward before exchange before update), or, where no
      operand has one, of its first consumer.  ``borrowed`` holds the
      names read so; what is still ``unnamed`` has no named neighbour.
    """
    text = step if isinstance(step, str) else step.as_text()
    computations, entry, lines = {}, None, None
    for line in text.splitlines():
        if line.startswith("  ") and lines is not None:
            name, equals, rest = line.lstrip(" ").partition(" = ")
            ran = _RAN.search(rest)
            if not equals or not ran:
                continue
            names = _OP_NAME.search(rest)
            lines.append((
                name.removeprefix("ROOT ").lstrip("%"),
                names.group(1).split(";")[0] if names else "",
                ran.group(1) == "fusion",
                re.findall(r"%([\w.\-]+)", ran.group(2)),
                [c for group in _CALLED.findall(rest)
                 for c in re.findall(r"[\w.\-]+", group)]))
            continue
        head = _HEAD.match(line)
        if head:
            lines = computations[head.group(2)] = []
            entry = head.group(2) if head.group(1) else entry
    instructions, fused, borrowed = {}, {}, set()
    todo, seen = [(entry, "")], []
    while todo:
        computation, loop = todo.pop()
        if computation in seen or computation not in computations:
            continue
        seen.append(computation)
        for name, op_name, is_fusion, _, called in computations[computation]:
            whole = f"{loop}/{op_name}" if loop and op_name else (
                op_name or loop)
            instructions[name] = (phase_of(whole), scope_of(whole))
            if is_fusion:
                fused[name] = {
                    phase_of(f"{loop}/{inner}" if loop else inner)
                    for c in called
                    for _, inner, _, _, _ in computations.get(c, ())
                    if "/" in inner}
            else:
                todo.extend((c, whole) for c in called)

    def named(name):
        return instructions.get(name, ("unnamed",))[0] != "unnamed"

    for computation in seen:
        lines, consumer = computations[computation], {}
        for name, _, _, operands, _ in lines:  # operands come first
            for operand in operands:
                consumer.setdefault(operand, name)
            around = [instructions[o] for o in operands if named(o)]
            if not named(name) and around:
                # of several in that phase the last: a kernel's weights
                # come after what it reads of the routing
                instructions[name] = max(
                    reversed(around),
                    key=lambda found: PHASES.index(found[0]))
                borrowed.add(name)
        for name, _, _, _, _ in reversed(lines):  # consumers come last
            if not named(name) and named(consumer.get(name)):
                instructions[name] = instructions[consumer[name]]
                borrowed.add(name)
    return instructions, fused, borrowed
