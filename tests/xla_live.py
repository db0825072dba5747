"""What is live in device memory at each moment of a compiled step, read
off XLA's own dump: the calibration of
``horovod_tpu/models/transformer.py:kept_plan``, which charges each
moment of the backward pass what it read here.

``lowered.compile(compiler_options={"xla_dump_to": directory,
"xla_dump_hlo_as_text": True})`` (:func:`compile_with_dump`) leaves a
``*buffer-assignment.txt`` with the scheduled order of the instructions,
every value's allocation, offset and size, and its live range in that
order.  :class:`Dump` reads it and sums, position by position, the
values live in the HBM heap (the ``preallocated-temp`` allocation of
color 0; color 1 is VMEM); the step's arguments (parameters, optimizer
state, inputs) are resident beside them.  The sum leaves out what the
allocator loses between values (0.01 to 1.1 GiB at the cells' peaks), so
it is a lower bound of ``memory_analysis()`` and the right thing to hold
a moment of the plan against.

    JAX_PLATFORMS=cpu python tests/xla_live.py <workload> [adamw|clip]

compiles a cell of the benchmark for a described v5e as
``tests/test_chip_compile.py`` does (several minutes and GiB at full
depth: one cell a call), under the cell's fused AdamW or under
``chain(clip_by_global_norm, adamw)``, which holds every gradient to the
end, and prints the plan's moments beside the compiled step's, and what
is live where the step peaks.  Nothing executes."""

import collections
import glob
import os
import re
import shutil
import sys


def compile_with_dump(lowered, directory):
    """``lowered`` compiled with XLA's dump in ``directory`` (emptied
    first)."""
    shutil.rmtree(directory, ignore_errors=True)
    return lowered.compile(compiler_options={
        "xla_dump_to": str(directory), "xla_dump_hlo_as_text": True})


def _one(directory, *patterns):
    for pattern in patterns:
        found = glob.glob(os.path.join(str(directory), pattern))
        if found:
            return found[0]
    raise FileNotFoundError(f"no {patterns} under {directory}")


class Dump:
    """A compiled module's schedule and buffers: ``sequence`` (the
    instructions in scheduled order), ``op_name`` (an instruction's
    ``metadata.op_name``, the scopes JAX gave it), ``operands``,
    ``arguments`` (the bytes of the entry computation's parameters),
    ``heap`` (a value of the HBM heap -> ``(offset, size, first,
    last)``, positions in ``sequence``) and ``live`` (bytes of the heap
    in use at each position)."""

    def __init__(self, directory):
        self.op_name, self.operands = {}, {}
        for line in open(_one(
                directory, "*after_optimizations_after_buffer_assignment.txt",
                "*after_optimizations.txt")):
            found = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
            if not found:
                continue
            name = re.search(r'op_name="([^"]*)"', line)
            self.op_name[found.group(1)] = name.group(1) if name else ""
            call = re.search(r" [\w\-]+\((%[^)]*)\)", line)
            self.operands[found.group(1)] = re.findall(
                r"%([\w.\-]+)", call.group(1)) if call else []
        self.sequence, self.arguments = [], 0
        placed, ranges, heap, section = {}, {}, False, None
        for line in open(_one(directory, "*buffer-assignment.txt")):
            found = re.match(r"allocation \d+: size (\d+), (.*)", line)
            if found:
                heap = (found.group(2).startswith("preallocated-temp")
                        and "color" not in found.group(2))
                if found.group(2).startswith("parameter"):
                    self.arguments += int(found.group(1))
                continue
            found = re.match(
                r" value: <\d+ (\S+) @\d+> \(size=(\d+),offset=(\d+)\)", line)
            if found:
                if heap:
                    name = found.group(1)
                    placed[name if name.endswith("}") else name + "{}"] = (
                        int(found.group(3)), int(found.group(2)))
                continue
            if line.startswith("Used values:"):
                heap = False
            elif "InstructionSequence:" in line:
                section = self.sequence
            elif "BufferLiveRange:" in line:
                section = ranges
            elif "Live ranges at" in line:
                section = None
            elif section is self.sequence:
                found = re.match(r"\s+\d+:(\S+)", line)
                if found:
                    section.append(found.group(1))
            elif section is ranges:
                found = re.match(r"\s+(\S+):(\d+)-(\d+)", line)
                if found:
                    section[found.group(1)] = (
                        int(found.group(2)), int(found.group(3)))
        self.heap = {name: at + ranges[name]
                     for name, at in placed.items() if name in ranges}
        starts, stops = (collections.defaultdict(set) for _ in range(2))
        for name, (_, _, first, last) in self.heap.items():
            starts[first].add(name)
            stops[last + 1].add(name)
        self.live, now = [], set()
        for t in range(len(self.sequence)):
            now = (now - stops[t]) | starts[t]
            self.live.append(self._bytes(now))

    def live_at(self, t):
        """The heap's values live at position ``t``, one a place (values
        that share a buffer are one)."""
        seen, found = set(), []
        for name, (offset, size, first, last) in sorted(
                self.heap.items(), key=lambda item: -item[1][1]):
            if first <= t <= last and offset not in seen:
                seen.add(offset)
                found.append(name)
        return found

    def _bytes(self, names):
        """The bytes ``names`` cover (the union of their extents)."""
        total, end = 0, -1
        for offset, size in sorted(self.heap[name][:2] for name in names):
            if offset + size > end:
                total += offset + size - max(offset, end)
                end = offset + size
        return total

    def source(self, value):
        """The scopes of the instruction that made ``value``: its own
        ``op_name`` or, for a copy the compiler put in, its operand's."""
        name = re.sub(r"\{[\d,]*\}$", "", value)
        for _ in range(6):
            if self.op_name.get(name) or not self.operands.get(name):
                break
            name = self.operands[name][0]
        return self.op_name.get(name, "")

    def windows(self):
        """``{moment: (first, last)}``, positions in ``sequence``, in the
        plan's names and order: ``"head"`` (from the start of the step to
        the backward pass's first block, so the forward pass is in it)
        and ``"block <l>"`` from where the backward pass enters block
        ``l`` (the tenth of its instructions: a few are scheduled far
        ahead) to where it enters the next; the block of a next-token
        module (scope ``mtp``) is ``"block <n_layers>"``."""
        at = collections.defaultdict(list)
        for t, name in enumerate(self.sequence):
            scopes = self.op_name.get(name, "")
            block = re.search(r"/checkpoint/(?:rematted_computation/)?"
                              r"(?:block_(\d+)|(block))/", scopes)
            if block and "transpose(" in scopes:
                at[block.group(1) and int(block.group(1))].append(t)
        if None in at:  # the module's block, after the model's last
            at[max((k for k in at if k is not None), default=-1) + 1] = (
                at.pop(None))
        marks = sorted((ts[len(ts) // 10], layer) for layer, ts in at.items())
        ends = [t for t, _ in marks[1:]] + [len(self.sequence)]
        found = {"head": (0, marks[0][0])}
        found.update({f"block {layer}": (t, end)
                      for (t, layer), end in zip(marks, ends)})
        return found

    def moments(self):
        """``{moment: (bytes, position)}``: the arguments and the heap in
        use where it is largest inside each of :meth:`windows`."""
        found = {}
        for moment, (first, last) in self.windows().items():
            t = max(range(first, last), key=self.live.__getitem__)
            found[moment] = (self.arguments + self.live[t], t)
        return found


def report(dump, plan, out=sys.stdout):
    """The plan's moments beside the compiled step's, and what is live
    where the step peaks."""
    gib = 2 ** 30
    compiled = dump.moments()
    for moment, predicted in plan.moments or ():
        got = compiled.get(moment)
        print(f"  {moment:9s} plan {predicted / gib:7.3f} GiB" + (
            f"  compiled {got[0] / gib:7.3f} ({(predicted - got[0]) / gib:+.3f})"
            f" at {got[1]}" if got else ""), file=out)
    moment, (_, t) = max(compiled.items(), key=lambda item: item[1][0])
    print(f"  live at the compiled peak ({moment}, position {t} of "
          f"{len(dump.sequence)}: {dump.source(dump.sequence[t])[-90:]}):",
          file=out)
    for name in dump.live_at(t)[:40]:
        print(f"    {dump.heap[name][1] / 2 ** 20:9.1f} MiB  {name:36s} "
              f"{dump.source(name)[-100:]}", file=out)


def main(workload, optimizer="adamw", directory=None):
    """Compile ``workload``'s step for one described v5e and report."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [repo, os.path.join(repo, "tests", "benchmark")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark_toy import load_by_path
    from horovod_tpu.models import transformer as program
    from horovod_tpu.parallel import make_mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the model's TPU branch
    jax.config.update("jax_enable_compilation_cache", False)
    bench = load_by_path(os.path.join(repo, "benchmark", "run.py"),
                         "hvd_benchmark_run_xla_live")
    mesh = make_mesh({"hvd": 1}, devices=list(topo.devices)[:1])
    cell = bench.load_cell(repo, workload)
    base = optax.adamw(**cell.job["optimizer"]["args"])
    if optimizer == "clip":
        base = optax.chain(optax.clip_by_global_norm(1.0), base)
    opt, step = cell.loop.make_step(cell, base, mesh)
    params, extra = jax.eval_shape(
        lambda key: cell.family.init(cell.config, cell.job, key),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (cell.job["per_chip_batch"], cell.job["seq_len"]), jnp.int32)
    program.device_memory_bytes = lambda: (16_911_433_728, None)
    plans, was = [], program.kept_plan
    program.kept_plan = lambda *a, **k: plans.append(was(*a, **k)) or plans[-1]

    def shaped(tree, spec):
        at = NamedSharding(mesh, spec)
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=at), tree)

    directory = directory or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"xla_live_{workload}_{optimizer}")
    compiled = compile_with_dump(step.lower(
        shaped(params, P()), shaped(extra, P()),
        shaped(jax.eval_shape(opt.init, params), P()),
        shaped(tokens, P("hvd"))), directory)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    plan = next((p for p in reversed(plans) if p.moments), plans[-1])
    print(plan)
    print(f"  compiled {total / 2 ** 30:.3f} GiB by memory_analysis")
    report(Dump(directory), plan)


if __name__ == "__main__":
    main(*sys.argv[1:])
