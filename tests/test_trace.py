"""The eager plane's own record of a request's life
(``horovod_tpu/utils/trace.py``): spans in the profiler's file and the
request log, under the native and the Python controller.

Each controller serves one process of its own (``hvd.init`` and
``hvd.shutdown`` are its to call; the session's ``hvd`` fixture may live
in this one): eight ranks hand in one allreduce each outside any trace,
then again under ``jax.profiler``, and the process reports what the
trace and the log hold.
"""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.common.handles import Handle
from horovod_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANKS = 8

DRIVER = """
import glob, json, os, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics
from horovod_tpu.utils import trace

controller, out_dir = sys.argv[1], sys.argv[2]
hvd.init(controller=controller)


def one_allreduce(rank):
    return hvd.synchronize(hvd.allreduce_async(
        jnp.full((4,), float(rank)), op=hvd.Sum, name="t"))


basics.run_parallel(one_allreduce)  # compiles; no trace is active
report = {{"served_by": type(basics._get_state().controller).__name__,
           "log_untraced": len(trace.LOG),
           "files_untraced": os.listdir(out_dir)}}
trace.reset()
options = jax.profiler.ProfileOptions()
options.python_tracer_level = 0
options.host_tracer_level = 1
jax.profiler.start_trace(out_dir, profiler_options=options)
basics.run_parallel(one_allreduce)
report["log"] = list(trace.LOG)
report["stats"] = hvd.eager_stats()
# The trace ends on an event, not on a clock: the dispatcher leaves
# hvd.execute after it has handed out the results and goes back into
# hvd.wait_batch; shutdown joins it, so every span it was in is closed
# and in the file, however loaded the machine is.
hvd.shutdown()
jax.profiler.stop_trace()
path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                              "*.xplane.pb"))[-1]
report["lines"] = [
    [(e.name, e.start_ns, e.start_ns + e.duration_ns)
     for e in line.events if e.name.startswith("hvd.")]
    for plane in jax.profiler.ProfileData.from_file(path).planes
    if plane.name == "/host:CPU" for line in plane.lines]
report["log_after_shutdown"] = len(trace.LOG)
trace.reset()
report["log_after_reset"] = len(trace.LOG)
print(json.dumps(report))
"""

CALLER = {"hvd.submit", "hvd.wait"}
# an allreduce response: the ranks' tensors go to the collective program
# as they are (assemble), so no hvd.exec.fuse_in / hvd.exec.stack, the
# staging spans of the other collectives
EXECUTE = {"hvd.execute", "hvd.exec.assemble", "hvd.exec.lookup",
           "hvd.exec.launch", "hvd.exec.complete"}
CORE = {"hvd.wait_batch", "hvd.decode", "hvd.mark_done"}


@pytest.fixture(scope="module", params=["native", "python"])
def report(request, tmp_path_factory):
    out_dir = tmp_path_factory.mktemp("trace_" + request.param)
    done = subprocess.run(
        [sys.executable, "-c", DRIVER.format(repo=REPO), request.param,
         str(out_dir)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=180)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    out["asked_for"] = request.param
    out["lines"] = [line for line in out["lines"] if line]
    return out


def test_the_controller_asked_for_serves(report):
    assert report["served_by"] == {
        "native": "NativeController",
        "python": "PythonController"}[report["asked_for"]]


def test_every_span_of_the_table_is_in_the_trace(report):
    names = {name for line in report["lines"] for name, _, _ in line}
    core = CORE if report["asked_for"] == "native" else set()
    assert names == CALLER | EXECUTE | core


def test_exec_spans_lie_inside_an_execute_span(report):
    seen = 0
    for line in report["lines"]:
        executes = [(s, e) for name, s, e in line if name == "hvd.execute"]
        for name, start, end in line:
            if name.startswith("hvd.exec."):
                seen += 1
                assert any(s <= start and end <= e for s, e in executes), name
    # one response: each of the four once, whatever the number of ranks
    assert seen == 4


def test_callers_and_the_dispatcher_are_threads_apart(report):
    callers = [line for line in report["lines"]
               if any(name == "hvd.submit" for name, _, _ in line)]
    assert len(callers) == RANKS
    for line in callers:
        assert [name for name, _, _ in sorted(
            line, key=lambda e: e[1])] == ["hvd.submit", "hvd.wait"]
    (dispatcher,) = [line for line in report["lines"]
                     if line not in callers]
    assert {name for name, _, _ in dispatcher} >= EXECUTE


def test_nothing_is_recorded_with_no_trace_active(report):
    """The untraced allreduces ran the same spans: none is in the file,
    and no file was there before the trace began."""
    assert report["files_untraced"] == []
    submits = sum(name == "hvd.submit" for line in report["lines"]
                  for name, _, _ in line)
    assert submits == RANKS


def test_the_log_has_one_ordered_record_a_request(report):
    # always on: the untraced requests were logged too
    assert report["log_untraced"] == RANKS
    assert len(report["log"]) == RANKS
    assert len({record[0] for record in report["log"]}) == RANKS
    for record in report["log"]:
        assert len(record) == 6
        assert sorted(record[2:]) == record[2:] and record[2] > 0


def test_requests_of_one_response_share_its_id(report):
    """Eight ranks' requests for one tensor are one response."""
    assert len({record[1] for record in report["log"]}) == 1
    assert len({record[4] for record in report["log"]}) == 1
    executes = sum(name == "hvd.execute" for line in report["lines"]
                   for name, _, _ in line)
    assert executes == 1


def test_the_log_survives_shutdown_and_reset_empties_it(report):
    assert report["log_after_shutdown"] == RANKS
    assert report["log_after_reset"] == 0


def test_eager_stats_counts_and_stages(report):
    stats = report["stats"]
    assert set(stats) == {"requests", "responses", "submit_us",
                          "queue_wait_us", "execute_us"}
    assert (stats["requests"], stats["responses"]) == (RANKS, 1)
    start, done = report["log"][0][4], max(r[5] for r in report["log"])
    assert stats["execute_us"] == pytest.approx((done - start) / 1e3)


# ------------------------------------------- the log alone, no plane
@pytest.fixture
def log():
    trace.reset()
    yield trace.LOG
    trace.reset()


def stamped(t_submit, t_enqueued, t_execute_start, response_id=7):
    handle = Handle("t")
    trace.submitted(handle, t_submit)
    handle.t_enqueued = t_enqueued
    handle.response_id, handle.t_execute_start = response_id, t_execute_start
    return handle


@pytest.mark.parametrize("t_enqueued,logged", [
    (20, 20),   # back from enqueue before the dispatcher took it up
    (0, 30),    # taken up before the caller stamped it
    (35, 30),   # stamped after it was taken up
])
def test_the_enqueue_stamp_is_held_to_the_start_of_execution(
        t_enqueued, logged, log):
    handle = stamped(10, t_enqueued, 30)
    handle.set_result("r")
    (record,) = log
    assert record[:5] == (handle.request_id, 7, 10, logged, 30)
    assert record[5] >= trace.now() - 10**9


def test_a_handle_no_response_took_up_is_not_logged(log):
    """A join's handle, or one a test hands to the executor itself."""
    Handle("join").set_result(3)
    assert not log


def test_a_failed_request_is_not_logged(log):
    handle = stamped(10, 20, 30)
    handle.set_error("no")
    handle.set_result("late")  # first completion wins
    assert not log


def test_a_result_is_logged_once_and_before_the_waiter_wakes(log):
    handle = stamped(10, 20, 30)
    handle.set_result("r")
    handle.set_result("again")
    assert len(log) == 1 and handle.wait(0) == "r"


def test_executing_stamps_every_handle_of_a_response(log):
    from horovod_tpu.ops.python_controller import GroupEntry

    entries = [GroupEntry(name, (4,), "float32", {}, {
        rank: Handle(name) for rank in range(2)}) for name in "ab"]
    trace.executing(entries, 50)
    trace.executing(entries[:1], 60)
    first, second = (entry.handles for entry in entries)
    assert {h.t_execute_start for h in first.values()} == {60}
    assert {h.t_execute_start for h in second.values()} == {50}
    assert (first[0].response_id == first[1].response_id
            == second[0].response_id + 1)


def test_eager_stats_of_an_empty_log(log):
    assert trace.eager_stats() == {"requests": 0, "responses": 0}


def test_eager_stats_means(log):
    """Two responses: one of two requests that took 40 and 60 us to the
    last result, one of one request that took 10."""
    log.extend([(1, 1, 0, 2_000, 10_000, 50_000),
                (2, 1, 1_000, 5_000, 10_000, 70_000),
                (3, 2, 80_000, 81_000, 90_000, 100_000)])
    assert trace.eager_stats() == {
        "requests": 3, "responses": 2,
        "submit_us": pytest.approx((2 + 4 + 1) / 3),
        "queue_wait_us": pytest.approx((8 + 5 + 9) / 3),
        "execute_us": pytest.approx((60 + 10) / 2)}


def test_the_log_is_bounded():
    assert trace.LOG.maxlen == 65536


# ------------------------------------ the batch log alone, no prefetcher
@pytest.fixture
def batches():
    trace.reset()
    yield trace.BATCHES
    trace.reset()


def test_input_stats_of_an_empty_log(batches):
    import horovod_tpu as hvd

    assert hvd.input_stats is trace.input_stats
    # no batch, no share of batches: ``reused_share`` is left out too
    assert hvd.input_stats() == {"batches": 0, "bytes": 0}


def test_input_stats_shares_means_and_medians(batches):
    """Four batches of 100 bytes: one the loop found nothing staged for,
    one whose copy was still under way when it was taken; the first
    gathered into a new set of host arrays, the rest into kept ones."""
    ms = 1_000_000
    batches.extend([
        # id, bytes, next, host, put, asked, taken, depth, ready, reused
        (1, 100, 0, 50 * ms, 80 * ms, 70 * ms, 82 * ms, 0, True, False),
        (2, 100, 80 * ms, 120 * ms, 160 * ms, 200 * ms, 201 * ms, 1, True,
         True),
        (3, 100, 160 * ms, 220 * ms, 240 * ms, 300 * ms, 301 * ms, 2, False,
         True),
        (4, 100, 240 * ms, 310 * ms, 360 * ms, 400 * ms, 402 * ms, 2, True,
         True)])
    assert trace.input_stats() == {
        "batches": 4, "bytes": 400, "starved_share": 0.5,
        "wait_ms": pytest.approx((12 + 1 + 1 + 2) / 4),
        "source_ms": pytest.approx((50 + 60) / 2),
        "put_ms": pytest.approx((30 + 40) / 2),
        "reused_share": 0.75}


@pytest.mark.parametrize("reused,share", [
    ([False] * 3, 0.0), ([True] * 3, 1.0)])
def test_input_stats_reused_share_counts_the_tenth_field(batches, reused,
                                                         share):
    batches.extend((i, 8, 0, 1, 2, 3, 4, 1, True, r)
                   for i, r in enumerate(reused))
    assert trace.input_stats()["reused_share"] == share


def test_a_batch_is_logged_when_it_is_taken_not_when_it_is_staged(batches):
    import jax.numpy as jnp

    batch = {"x": jnp.zeros((4, 8), jnp.float32), "y": jnp.zeros(4, jnp.int32)}
    staged = trace.batch_staged(9, batch, 10, 20, True)
    assert staged[:4] == (9, 4 * 8 * 4 + 4 * 4, 10, 20)
    assert staged[5] is True  # gathered into a kept set
    assert staged[6] is batch["x"]  # the largest array
    assert not batches
    asked = trace.now()
    trace.batch_taken(staged, asked, 2)
    (record,) = batches
    assert record[:4] == staged[:4] and record[4] == staged[4]
    # ready_at_take in the ninth place as ever, reused after it
    assert record[5] == asked and record[7:] == (2, True, True)
    assert staged[4] <= asked <= record[6] <= trace.now()
    # an empty batch has no array to ask: nothing is still under way
    trace.batch_taken(trace.batch_staged(10, {}, 10, 20, False), asked, 0)
    assert batches[-1][1] == 0 and batches[-1][8:] == (True, False)


def test_both_logs_are_bounded_and_reset_together(batches):
    assert trace.BATCHES.maxlen == trace.LOG.maxlen == 65536
    batches.append((1, 1, 0, 1, 2, 3, 4, 0, True, False))
    trace.LOG.append((1, 1, 0, 1, 2, 3))
    trace.reset()
    assert not trace.BATCHES and not trace.LOG


def test_the_input_paths_instruments_cost_under_20_us_a_batch(batches):
    """Every call ``prefetch_to_device`` makes into this module for one
    batch, with no profiler trace active: three spans (flag checks),
    the clock, the producer's half of the record, the log's line.  In
    this thread's CPU time and as the best of seven rounds of 1,000, so
    that neighbours on a shared core are not read as the instruments'
    cost (the suite runs six workers wide)."""
    import time

    import jax.numpy as jnp

    batch = {"image": jnp.zeros((4, 8), jnp.float32),
             "label": jnp.zeros(4, jnp.int32)}

    def one_batch():
        batch_id = next(trace.batch_ids)
        t_next_start = trace.now()
        with trace.span("hvd.data.next", batch=batch_id):
            pass
        t_host_ready = trace.now()
        with trace.span("hvd.data.put", batch=batch_id):
            pass
        staged = trace.batch_staged(batch_id, batch, t_next_start,
                                    t_host_ready, True)
        t_asked = trace.now()
        with trace.span("hvd.data.wait") as wait:
            wait.set_metadata(batch=staged[0])
        trace.batch_taken(staged, t_asked, 2)

    rounds = []
    for _ in range(7):
        start = time.thread_time()
        for _ in range(1000):
            one_batch()
        rounds.append((time.thread_time() - start) / 1000)
    assert len(batches) == 7000
    assert min(rounds) < 20e-6, rounds


# ------------------------- the compiled step's names, no program run
@pytest.mark.parametrize("op_name,phase,scope", [
    ("jit(per_shard)/jvp(Transformer)/block_3/attn/attn/latent/mul",
     "forward", "block/attn/latent"),
    ("jit(per_shard)/transpose(jvp(Transformer))/jvp(Transformer)/"
     "checkpoint/rematted_computation/block_3/ln1/mul",
     "recompute", "block/ln"),
    ("jit(per_shard)/transpose(jvp(Transformer))/jvp(Transformer)/"
     "checkpoint/block_3/moe/moe/experts/select_n",
     "backward", "block/moe/experts"),
    ("jit(per_shard)/transpose(jvp(mtp))/NextTokenModule/jvp(mtp)/"
     "NextTokenModule/checkpoint/block/attn/attn/latent/add_any",
     "backward", "mtp/NextTokenModule/block/attn/latent"),
    ("jit(per_shard)/jvp(Transformer)/loop/while/body/closed_call/"
     "Transformer.one_pass/block_2/mlp/jit(silu)/logistic",
     "forward", "loop/block/mlp"),
    ("jit(per_shard)/jvp(loss)/exit_loss/jit(log_sigmoid)/jit(softplus)/"
     "log1p", "forward", "loss/exit_loss"),
    ("jit(per_shard)/transpose(jvp(loss))/pallas_call", "backward", "loss"),
    ("jit(per_shard)/jvp(ResNet)/BottleneckBlock_12/Conv_0/"
     "conv_general_dilated", "forward", "BottleneckBlock/Conv"),
    ("jit(per_shard)/jvp(Transformer)/embed/jit(_take)/scatter-add",
     "forward", "embed"),
    ("jit(per_shard)/shard_map/hvd/exchange/psum", "exchange",
     "hvd/exchange"),
    ("jit(per_shard)/hvd/update/hvd/exchange/psum", "exchange",
     "hvd/update/hvd/exchange"),
    ("jit(per_shard)/shard_map/hvd/update/jit(_where)/select_n", "update",
     "hvd/update"),
    # the caller's apply_updates and its pmean of the loss
    ("jit(per_shard)/add", "update", ""),
    ("jit(per_shard)/shard_map/psum", "update", ""),
    # an argument's label, a bare primitive, no name
    ("params[\\'block_0\\'][\\'ln1\\'][\\'scale\\']", "unnamed", ""),
    ("reduce_sum", "unnamed", ""),
    ("", "unnamed", ""),
])
def test_phase_and_scope_of_an_op_name(op_name, phase, scope):
    assert trace.phase_of(op_name) == phase
    assert trace.scope_of(op_name) == scope


HLO = """HloModule jit_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/transpose(jvp(Model))/dense/dot_general"}
  %c = f32[]{:T(128)} constant(0.1)
  ROOT %add.1 = f32[8]{0} add(%dot.1, %p), metadata={op_name="jit(step)/hvd/update/add" stack_frame_id=3}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %ln = f32[8]{0} custom-call(%t), custom_call_target="tpu_custom_call", metadata={op_name="Model.one_pass/block_1/ln1/pallas_call"}
  %copy.7 = f32[8]{0} copy(%ln)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%t, %copy.7)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%t.1, %t.1), direction=LT
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

ENTRY %main.5 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0:T(8,128)S(1)} parameter(0), metadata={op_name="params[\\'w\\']"}
  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(Model)/loop/while"}
  %fusion.9 = f32[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(Model))/dense/dot_general"}
  %ragged-dot-none.1 = f32[8]{0:T(8,128)(2,1)} custom-call(%while.1, %fusion.9), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %iota.4 = s32[8]{0} iota(), iota_dimension=0
  ROOT %all-reduce.3 = f32[8]{0} all-reduce(%copy-done.2), to_apply=%region_0.1, metadata={op_name="jit(step)/shard_map/hvd/exchange/psum;jit(step)/x"}
}
"""


def test_step_phases_of_a_hand_written_program():
    instructions, fused, borrowed = trace.step_phases(HLO)
    assert instructions == {
        "while.1": ("forward", "loop"),
        # a kernel in a loop keeps the end of its path, a copy the
        # compiler made there has no name: both are their loop's
        "t": ("forward", "loop"),
        "ln": ("forward", "loop/block/ln"),
        "copy.7": ("forward", "loop"),
        "tuple.2": ("forward", "loop"),
        "t.1": ("forward", "loop"),
        "lt": ("forward", "loop"),
        # the first of the names the compiler joined
        "all-reduce.3": ("exchange", "hvd/exchange"),
        # where its own name says, whatever it holds
        "fusion.9": ("backward", "dense"),
        # what the compiler made and left unnamed: the kernel it lowered
        # a product to runs when the last of its operands exists (the
        # weights' cast is forward's, the gradient backward's); a
        # prefetch with no named operand is its first consumer's, and
        # so is the argument it fetches
        "ragged-dot-none.1": ("backward", "dense"),
        "copy-done.2": ("exchange", "hvd/exchange"),
        "copy-start.2": ("exchange", "hvd/exchange"),
        "x": ("forward", "loop"),
        # no named neighbour
        "iota.4": ("unnamed", ""),
    }
    # neither a fusion's instructions nor a reducer's are events
    assert not {"dot.1", "add.1", "sum"} & set(instructions)
    assert fused == {"fusion.9": {"backward", "update"}}
    assert borrowed == {"ragged-dot-none.1", "copy-done.2", "copy-start.2",
                        "x"}
    assert set(trace.PHASES) >= {p for p, _ in instructions.values()}


def test_step_phases_takes_a_compiled_step():
    import jax
    import jax.numpy as jnp

    def step(w, x):
        with jax.named_scope("hvd/update"):
            return w - 0.1 * jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)

    compiled = jax.jit(step).lower(jnp.ones((4, 4)),
                                   jnp.ones((2, 4))).compile()
    instructions, _, _ = trace.step_phases(compiled)
    assert instructions == trace.step_phases(compiled.as_text())[0]
    assert {"forward", "backward", "update"} <= {
        p for p, _ in instructions.values()}
