"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process a run, the only one that touches JAX: it loads the cell
that ``BENCHMARK.json`` names, builds the program's training step,
holds it to the plain reference, warms up, measures for ``--seconds``
and prints one JSON object as its last line.  Without a TPU of the
cell's chip count, or on a ``device_kind`` that ``peaks.json`` does not
hold, it exits non-zero and prints no result: there is no CPU branch
in ``main``.  (The tests call :func:`run_cell` on CPU devices.)

Whatever belongs to one configuration, traffic mix, loop or metric is a
file of its own that is found BY NAME; nothing here tests a name:

    configs/<config>.json            sizes, source, reduced, assumed, job
    models/<family>.py               program model, required operations,
                                     plain reference
    traffic/<mix>.json               loop, chips, log_every, pool, ...
    loops/<loop>.py                  how one training step is made
    end_to_end_metrics/<metric>.py   reader of a ``--trace 0`` metric
    layer_metrics/<metric>.py        reader of a ``--trace 1`` metric
    peaks.json                       the chip's published peaks
    trace_reduce.py                  .xplane.pb -> busy, operations, gaps
"""

import time

_PROCESS_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# fires once for every program compiled OR read from the persistent
# cache (seen on the chip, PR 22: 15 programs, 15 events cold and warm)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchmarkError(Exception):
    """The run cannot give a result; the message says why."""


def load_module(path):
    """A benchmark file as a module, by location (a cell names files,
    not packages, and a scratch copy of the benchmark must load its own)."""
    name = "hvd_benchmark_" + "_".join(
        os.path.normpath(os.path.abspath(path)).split(os.sep)[-3:])[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root, workload):
    """Everything the manifest under ``root`` says about one cell."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, manifest["paths"][0])
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(
        os.path.join(bench, "traffic", entry["traffic"] + ".json"))
    if traffic["chips"] != entry["chips"]:
        raise BenchmarkError(
            f"traffic {entry['traffic']!r} is written for "
            f"{traffic['chips']} chips, the cell asks for {entry['chips']}")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return types.SimpleNamespace(
        name=workload, root=root, bench=bench, chips=entry["chips"],
        config=config, traffic=traffic,
        # the job a configuration stands for; a mix may override a part
        job={**config["job"], **traffic.get("job", {})},
        family=load_module(
            os.path.join(bench, "models", config["family"] + ".py")),
        loop=load_module(
            os.path.join(bench, "loops", traffic["loop"] + ".py")),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m)],
        per_layer=[m for m in manifest["per_layer"] if applies(m)])


class Run:
    """One run of one cell: what the loop files and the metric readers
    are handed.  Holds the host spans, the split of set-up, the
    compiled programs and, at the end, the measurements."""

    def __init__(self, cell, devices, seed, seconds, peaks=None,
                 perturb_reference=None):
        self.cell, self.devices, self.seed = cell, list(devices), seed
        self.seconds, self.peaks = seconds, peaks
        # a term the reference gets wrong on purpose: the tests' proof
        # that the check can see one
        self.perturb_reference = perturb_reference
        self.spans = []      # (name, start_ns, end_ns), host clock
        self.setup = {}      # seconds of set-up by part
        self.programs = {}   # name -> compiled executable
        self.notes = {}      # whatever a loop wants on the earlier line
        self.measured = {}   # filled by run_cell
        self.reduced_trace = None
        import jax

        self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name):
        """A host span: kept in memory and, under a trace, written into
        the profiler's own file on the device trace's clock."""
        start = time.perf_counter_ns()
        with self._annotate(name):
            yield
        self.spans.append((name, start, time.perf_counter_ns()))

    @contextlib.contextmanager
    def phase(self, name):
        """A part of set-up, for the split printed before the result."""
        start = time.perf_counter()
        yield
        self.setup[name] = (self.setup.get(name, 0.0)
                            + time.perf_counter() - start)

    def reader(self, directory, name):
        """The reader of a metric, by name."""
        return load_module(os.path.join(self.cell.bench, directory,
                                        name + ".py"))

    def rate_per_chip(self, unit):
        """Units of completed steps a second a chip over the measured
        window, or ``None`` where the cell's family counts another."""
        cell = self.cell
        if cell.family.SAMPLE_UNIT != unit:
            return None
        return (self.measured["samples_per_s"] / len(self.devices)
                * cell.family.sample_units(cell.config, cell.job))

    def optimizer(self):
        """The job's optax optimizer, plain (no gradient exchange)."""
        import optax

        spec = self.cell.job["optimizer"]
        return getattr(optax, spec["name"])(**spec["args"])

    def inputs(self, state_sharding, batch_sharding):
        """Weights and batches made on the device from the seed, each in
        one jitted call placed as the step will return or take it."""
        import jax

        cell, family = self.cell, self.cell.family
        n = cell.job["per_chip_batch"] * len(self.devices)
        group = family.CHECK_GROUP
        if cell.job["per_chip_batch"] % group:
            raise BenchmarkError(
                f"per_chip_batch {cell.job['per_chip_batch']} is not a "
                f"multiple of the family's check group {group}")
        root_key = jax.random.PRNGKey(self.seed)
        init = jax.jit(
            lambda key: family.init(cell.config, cell.job, key),
            out_shardings=state_sharding)
        make = jax.jit(
            lambda key: family.make_batch(cell.config, cell.job, key, n),
            out_shardings=batch_sharding)
        small = jax.jit(
            lambda key: family.make_batch(cell.config, cell.job, key, group))
        tile = jax.jit(
            lambda b: jax.tree.map(
                lambda a: jax.numpy.concatenate([a] * (n // group)), b),
            out_shardings=batch_sharding)
        keys = jax.random.split(root_key, cell.traffic["pool_batches"] + 2)
        group_batch = small(keys[1])
        return types.SimpleNamespace(
            init=lambda: init(keys[0]),
            pool=[make(k) for k in keys[2:]],
            group=group_batch, tiled_group=tile(group_batch),
            samples_per_step=n)


@contextlib.contextmanager
def count_compiles():
    """Programs compiled or fetched from the persistent cache inside
    the block (``jax.monitoring`` duration events), and cache hits."""
    from jax import monitoring

    seen = {"compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            seen["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def relative(got, want):
    return abs(got - want) / abs(want)


def check_against_reference(run, loop):
    """Holds the cell's own compiled step to the family's plain
    reference, before the window.  Returns the failures as text.

    1. forward: the step's loss on pool batch 0 from the seeded weights
       against the reference's loss there;
    2. backward and update: two steps on a batch that repeats one small
       seeded group against the reference's forward-backward on the
       group, one optimizer step in float32 and a forward.  Compared is
       the CHANGE of the loss over the step, relative to itself.
    """
    import jax
    import optax

    cell, family = run.cell, run.cell.family
    tol = family.TOLERANCE

    def ref_loss(params, extra, batch):
        return family.reference_loss(cell.config, params, extra, batch,
                                     perturb=run.perturb_reference)

    def ref_two_steps(params, extra, group):
        opt = run.optimizer()
        (first, _), grads = jax.value_and_grad(
            ref_loss, has_aux=True)(params, extra, group)
        updates, _ = opt.update(grads, opt.init(params), params)
        second, _ = ref_loss(optax.apply_updates(params, updates), extra,
                             group)
        return first, second

    # the reference sees the seeded weights alone: the optimizer's
    # state is not made yet, so the float32 copies have the room
    params, extra = loop.inputs.init()
    want_forward = jax.jit(ref_loss)(params, extra, loop.inputs.pool[0])[0]
    want = jax.jit(ref_two_steps)(params, extra, loop.inputs.group)
    want_forward, want_first, want_second = (
        float(v) for v in jax.device_get((want_forward, *want)))
    del params, extra
    state, got_forward = loop.step(loop.init_state(), loop.inputs.pool[0])
    got_forward = float(got_forward)
    del state
    state = loop.init_state()
    state, got_first = loop.step(state, loop.inputs.tiled_group)
    state, got_second = loop.step(state, loop.inputs.tiled_group)
    got_first, got_second = float(got_first), float(got_second)
    del state

    seen = {
        "forward": relative(got_forward, want_forward),
        "group_forward": relative(got_first, want_first),
        "update": relative(got_second - got_first,
                           want_second - want_first),
    }
    run.notes["reference_check"] = {
        "system": [got_forward, got_first, got_second],
        "reference": [want_forward, want_first, want_second],
        "relative_error": seen, "tolerance": tol}
    limits = {"forward": tol["forward"], "group_forward": tol["forward"],
              "update": tol["update"]}
    return [f"{name}: off the reference by {err:.3g}, tolerance "
            f"{limits[name]:g}" for name, err in seen.items()
            if not err <= limits[name]]


def measure_window(run, loop, state):
    """The measured window: steps dispatched without blocking, the loss
    fetched every ``log_every`` steps as a user's script logs it, the
    clock read there; ends at the first fetch at or after
    ``--seconds``.  Every step counted has completed: a step's loss
    exists only when the steps before it are done.

    Throughput is read from the MEDIAN time of a block of ``log_every``
    steps, fetch to fetch: a one-chip machine shares its host's cores,
    and a neighbour's second of work landed in 6 of 25 one-chip runs
    and took up to 5% (SPMD) and 15% (eager) off steps over seconds
    (PERF.md, PR 22).
    """
    log_every = run.cell.traffic["log_every"]
    pool = loop.inputs.pool
    steps = failed = 0
    losses, fetched_at = [], []
    first_span = len(run.spans)
    start = time.perf_counter()
    while True:
        state, loss = loop.step(state, pool[steps % len(pool)])
        steps += 1
        if steps % log_every:
            continue
        with run.span("fetch_loss"):
            losses.append(float(loss))
        fetched_at.append(time.perf_counter())
        if not math.isfinite(losses[-1]):
            failed += log_every
        if fetched_at[-1] - start >= run.seconds:
            break
    blocks = [b - a for a, b in zip([start] + fetched_at, fetched_at)]
    run.measured.update(
        steps=steps, failed=failed, elapsed_s=fetched_at[-1] - start,
        block_s=blocks,
        samples_per_s=(log_every * loop.inputs.samples_per_step
                       / statistics.median(blocks)),
        first_loss=losses[0], last_loss=losses[-1],
        window_spans=run.spans[first_span:])
    return state


def traced_steps(run, loop, state, trace_dir):
    """After the window, in a ``--trace 1`` run: a few steps each ended
    by ``block_until_ready`` on the host clock, then the same number
    dispatched as in the window under ``jax.profiler``."""
    import jax

    n = run.cell.traffic["trace_steps"]
    pool = loop.inputs.pool
    blocked = []
    for i in range(n):
        start = time.perf_counter()
        state, loss = loop.step(state, pool[i % len(pool)])
        jax.block_until_ready((state, loss))
        blocked.append((time.perf_counter() - start) * 1e3)
    run.measured["blocked_step_ms"] = blocked

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans are our own
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with run.span("traced_window"):
            for i in range(n):
                state, loss = loop.step(state, pool[i % len(pool)])
            with run.span("fetch_loss"):
                float(loss)
            jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    run.measured["traced_steps"] = n
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise BenchmarkError(f"the profiler wrote no .xplane.pb under "
                             f"{trace_dir}")
    reducer = load_module(os.path.join(run.cell.bench, "trace_reduce.py"))
    run.reduced_trace = reducer.reduce_file(
        files[-1], span_names={name for name, _, _ in run.spans})
    return state


def memory_peak_bytes(run, live_bytes):
    """Peak on the fullest chip.  The allocator's own peak leaves out a
    program's temporaries on this runtime (PERF.md, PR 21), so the peak
    is also worked out: what was live at the window's start, plus the
    temporaries and the outputs that alias no argument of the largest
    program.  The larger of the two is reported."""
    stats = [d.memory_stats() or {} for d in run.devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    extra = 0
    for compiled in run.programs.values():
        analysis = compiled.memory_analysis()
        extra = max(extra, analysis.temp_size_in_bytes
                    + analysis.output_size_in_bytes
                    - analysis.alias_size_in_bytes)
    return max(peak, live_bytes + extra)


def read_metrics(run, entries, directory):
    """Calls the reader of each metric the manifest lists for this
    cell; a reader that finds nothing to read returns ``None`` and the
    metric is left out of the line."""
    out = {}
    for entry in entries:
        value = run.reader(directory, entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def run_cell(cell, devices, seed, seconds, trace, peaks=None,
             perturb_reference=None, log=print):
    """The whole run on ``devices``; returns the result line as a dict.
    ``log`` gets the earlier line (set-up split, loop notes)."""
    import jax

    run = Run(cell, devices, seed, seconds, peaks, perturb_reference)
    run.setup["import_and_devices"] = time.perf_counter() - _PROCESS_START
    # settings of the program a mix fixes (read at hvd.init)
    os.environ.update(cell.traffic.get("env", {}))
    with count_compiles() as seen:
        loop = cell.loop.build(run)
        try:
            with run.phase("reference_check"):
                failures = check_against_reference(run, loop)
                failures += loop.check()
            warmup_compiles = []
            with run.phase("warmup"):
                pool = loop.inputs.pool
                state = loop.init_state()
                for i in range(cell.traffic["warmup_steps"]):
                    before = seen["compiles"]
                    state, loss = loop.step(state, pool[i % len(pool)])
                    jax.block_until_ready((state, loss))
                    warmup_compiles.append(seen["compiles"] - before)
            live = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                       for d in run.devices)
            in_setup = dict(seen)
            setup_s = time.perf_counter() - _PROCESS_START
            run.measured["setup_s"] = setup_s
            state = measure_window(run, loop, state)
            in_window = seen["compiles"] - in_setup["compiles"]
            if in_window:
                failures.append(f"{in_window} programs were compiled "
                                f"inside the window")
            if trace:
                state = traced_steps(
                    run, loop, state,
                    os.path.join(cell.root, ".bench_trace", cell.name))
            failures += loop.check_after(state)
            del state
        finally:
            loop.close()

    measured = run.measured
    run.setup["other"] = setup_s - sum(run.setup.values())
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": memory_peak_bytes(run, live)}
    result = {"correct": not failures, "attempted": measured["steps"],
              "failed": measured["failed"]}
    if trace:
        reduced = run.reduced_trace
        if not reduced["devices"] or not reduced["busy_s"] > 0:
            raise BenchmarkError(
                "the trace holds no operation that ran on a device")
        result["metrics"] = read_metrics(run, cell.per_layer,
                                         "layer_metrics")
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        result["metrics"] = read_metrics(run, cell.end_to_end,
                                         "end_to_end_metrics")
    result["device"] = device
    # the earlier line: for whoever reads the log, not for the driver
    log(json.dumps({"setup_split_s": run.setup,
                    "setup_cache_hits": in_setup["cache_hits"],
                    "setup_compiles": in_setup["compiles"],
                    "warmup_compiles_by_step": warmup_compiles,
                    "window": {k: measured[k] for k in (
                        "steps", "elapsed_s", "block_s", "first_loss",
                        "last_loss")},
                    "notes": run.notes, "failures": failures}))
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)  # the program under test: horovod_tpu
    try:
        cell = load_cell(ROOT, args.workload)
        import jax

        # one fixed directory: the environment's, else the checkout's
        jax.config.update(
            "jax_compilation_cache_dir",
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise BenchmarkError(
                f"JAX found platform {devices[0].platform!r}, not a TPU; "
                f"the benchmark measures nothing elsewhere")
        if len(devices) < cell.chips:
            raise BenchmarkError(
                f"{len(devices)} chips attached, the cell asks for "
                f"{cell.chips}")
        peaks = load_json(os.path.join(cell.bench, "peaks.json"))
        kind = devices[0].device_kind
        if kind not in peaks["device_kinds"]:
            raise BenchmarkError(
                f"device_kind {kind!r} is not in peaks.json "
                f"({sorted(peaks['device_kinds'])}): no peak to hold a "
                f"share against")
        result = run_cell(cell, devices[:cell.chips], args.seed,
                          args.seconds, bool(args.trace),
                          peaks=peaks["device_kinds"][kind])
    except BenchmarkError as exc:
        sys.stderr.write(f"benchmark: {exc}\n")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
