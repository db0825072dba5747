"""The compiled step by phase and by scope in the device trace of the
traced steps: for the readers of ``step_forward_ms``,
``step_recompute_ms``, ``step_backward_ms``, ``step_update_ms`` and
``step_unnamed_share``, and by hand

    python benchmark/scope_trace.py <file.xplane.pb[.gz]> <step.hlo.txt>

which prints the scope x phase table of a trace and of the text of the
program it ran (``compiled.as_text()``; a ``--trace 1`` run leaves its
own beside the trace, ``.bench_trace/<cell>/step.hlo.txt``).

An ``XLA Ops`` event carries its instruction's text and nothing of a
``jax.named_scope`` (PERF.md section 3), but it starts with the
instruction's name, and the compiled program's text has that
instruction with ``metadata={op_name="..."}``: the scopes the program
set and the ``jvp`` / ``transpose`` / ``rematted_computation`` JAX
wrote itself.  ``horovod_tpu/utils/trace.py:step_phases`` reads the
text into ``{instruction: (phase, scope)}`` (the rule is its
docstring's); here the events' self times are joined to it by name.
What the compiler made and left unnamed (prefetches, layout copies,
the kernels of ``ragged_dot``) it reads as the instructions around it.
So no shape is looked for and no family is known: a new configuration
is read unasked.

A program from before ``step_phases`` (the parent of the PR that
brought it) and an untraced run give ``None``, and every reader leaves
its metric out.
"""

import glob
import os
import sys
import time
import types


def reduce_planes(reducer, planes, instructions, fused, borrowed, steps):
    """``planes`` as ``trace_reduce.planes_of`` gives them,
    ``instructions`` and ``fused`` as ``step_phases`` does.  Per chip,
    in the order of the planes' names, milliseconds of self time a
    step: ``busy_ms`` (the union of the operations), ``phase_ms`` by
    phase (every phase a key; they add up to ``busy_ms``), ``both_ms``
    by ``(scope, phase)``, ``mixed_ms`` by the phases found inside a
    fusion that holds more than one (the time is ALSO in the phase the
    fusion's own name gave it), ``borrowed_ms``, the time of the
    instructions that were read as their neighbours (ALSO in their
    phases), and ``strangers_ms``, the part of ``unnamed`` whose events
    are no instruction of the program."""
    from horovod_tpu.utils.trace import PHASES

    chips = []
    for plane, lines in sorted(planes.items()):
        ops = lines.get(reducer.OP_LINE)
        if not reducer.DEVICE_PLANE.match(plane) or not ops:
            continue
        chip = types.SimpleNamespace(
            busy_ms=reducer.measure(reducer.union(
                (s, e) for _, s, e in ops)) / 1e6 / steps,
            phase_ms=dict.fromkeys(PHASES, 0.0), both_ms={}, mixed_ms={},
            borrowed_ms=0.0, strangers_ms=0.0)
        for text, self_ns in reducer.self_times(ops):
            name = reducer.parse(text)[0]
            ms = self_ns / 1e6 / steps
            phase, scope = instructions.get(name, ("unnamed", ""))
            chip.phase_ms[phase] += ms
            chip.both_ms[scope, phase] = chip.both_ms.get(
                (scope, phase), 0.0) + ms
            if name not in instructions:
                chip.strangers_ms += ms
            if name in borrowed:
                chip.borrowed_ms += ms
            inside = tuple(sorted(fused.get(name, ())))
            if len(inside) > 1:
                chip.mixed_ms[inside] = chip.mixed_ms.get(inside, 0.0) + ms
        chips.append(chip)
    return chips


def worst(run, of):
    """``of(chip)`` on the chip where it is largest, or ``None``."""
    chips = read(run)
    return max(map(of, chips)) if chips else None


def read(run):
    """The traced steps of this run by phase and scope, per chip,
    parsed once; the step's text is left beside the trace, the split by
    phase on the run's earlier line."""
    if run.reduced_trace is None or "step" not in run.programs:
        return None
    if not hasattr(run, "scope_trace"):
        run.scope_trace = None
        try:
            from horovod_tpu.utils.trace import step_phases
        except ImportError:
            return None
        start = time.perf_counter()
        trace_dir = os.path.join(run.cell.root, ".bench_trace",
                                 run.cell.name)
        # the glob of run.py's traced_steps
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        text = run.programs["step"].as_text()
        with open(os.path.join(trace_dir, "step.hlo.txt"), "w") as f:
            f.write(text)
        reducer = run.reader(".", "trace_reduce")
        run.scope_trace = reduce_planes(
            reducer, reducer.planes_of(reducer.load(files[-1])),
            *step_phases(text), run.measured["traced_steps"])
        # on the run's earlier line: the whole split (exchange and the
        # fusions that span phases are on no metric) and what reading
        # it cost this traced run, after its window
        run.notes["step_ms_by_phase"] = [
            {"busy": chip.busy_ms, **chip.phase_ms,
             "read_as_their_neighbours": chip.borrowed_ms,
             "fusions_that_hold": {
                "+".join(inside): ms for inside, ms in chip.mixed_ms.items()}}
            for chip in run.scope_trace]
        run.notes["scope_trace_s"] = time.perf_counter() - start
    return run.scope_trace


def table(chip, floor=0.001):
    """The chip's scope x phase table as lines of text: a row for every
    scope and for every prefix of one (``block``, ``block/attn``,
    ``block/attn/latent``), each the sum of what lies under it, the
    largest first, and ``(itself)`` for what a scope with children
    holds beside them; rows under ``floor`` of busy time are left out."""
    from horovod_tpu.utils.trace import PHASES

    rows = {}

    def add(row, phase, ms):
        rows.setdefault(row, dict.fromkeys(PHASES, 0.0))[phase] += ms

    scopes = {tuple(scope.split("/")) if scope else ()
              for scope, _ in chip.both_ms}
    for (scope, phase), ms in chip.both_ms.items():
        parts = tuple(scope.split("/")) if scope else ()
        for depth in range(len(parts) + 1):
            add(parts[:depth], phase, ms)
        if any(s[:len(parts)] == parts and len(s) > len(parts)
               for s in scopes):
            add(parts + ("(itself)",), phase, ms)
    out = [f"{'ms a step':<48}" + "".join(f"{p:>10}" for p in PHASES)
           + f"{'total':>10}"]

    def walk(row):
        total = sum(rows[row].values())
        if row and total < floor * chip.busy_ms:
            return
        label = "  " * (len(row) - 1) + "/".join(row) if row else "step"
        out.append(f"{label:<48}" + "".join(
            f"{rows[row][p]:10.2f}" for p in PHASES) + f"{total:10.2f}")
        children = [r for r in rows
                    if len(r) == len(row) + 1 and r[:len(row)] == row]
        for child in sorted(children, key=lambda r: -sum(rows[r].values())):
            walk(child)

    walk(())
    out.append(f"busy {chip.busy_ms:.2f} ms a step; {chip.borrowed_ms:.2f} "
               f"in instructions with no name of their own, read as their "
               f"neighbours; of unnamed, {chip.strangers_ms:.2f} in events "
               f"that are no instruction of this program")
    for inside, ms in sorted(chip.mixed_ms.items(), key=lambda kv: -kv[1]):
        out.append(f"fusions that hold {' + '.join(inside)}: {ms:.2f} ms "
                   f"(counted above where their own names say)")
    return out


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [here, os.path.dirname(here)]  # trace_reduce; horovod_tpu
    import trace_reduce
    from horovod_tpu.utils.trace import step_phases

    with open(sys.argv[2]) as f:
        phases = step_phases(f.read())
    planes = trace_reduce.planes_of(trace_reduce.load(sys.argv[1]))
    launches = max(len(lines.get(trace_reduce.MODULE_LINE, ()))
                   for lines in planes.values())
    for i, chip in enumerate(reduce_planes(
            trace_reduce, planes, *phases, max(launches, 1))):
        print(f"chip {i}, {launches} launches")
        print("\n".join(table(chip)))
