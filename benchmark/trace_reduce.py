"""From a profiler trace (``.xplane.pb``) to what the metrics read.

    python benchmark/trace_reduce.py <file.xplane.pb[.gz]>   # look by hand

Read with nothing but JAX (``jax.profiler.ProfileData``).  What the
v5e's trace looks like, as seen by hand in PR 22 (PERF.md section 3):

- one plane ``/device:TPU:<n>`` a chip (beside ``#Chip<n> Host
  Interface``, ``#Chip<n> Misc``, ``/host:metadata``, ``/host:CPU``,
  ``/device:CUSTOM:Megascale Trace``, ``Task Environment``);
- on it the line ``XLA Ops`` holds one event per executed HLO
  instruction, serial, NAMED BY THE INSTRUCTION'S WHOLE TEXT
  (``%fusion.12 = f32[1024,50257]{...} fusion(...), kind=kOutput, ...``);
  ``XLA Modules`` one event per program launch
  (``jit_per_shard(<fingerprint>)``); ``Steps`` one per launch too;
  ``Async XLA Ops`` the asynchronous instructions from their start to
  their done (``copy-start``, and the collectives' ``*-start``), which
  overlap the serial line;
- a Pallas kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``, named after the kernel
  function (``%attn.107``, ``%transpose_jvp___.1``, ``%pallas_call.829``);
  XLA's convolutions sit inside ``fusion`` instructions (``kind=kOutput``)
  and have no name of their own;
- the benchmark's host spans (``jax.profiler.TraceAnnotation``) are on
  the ``/host:CPU`` plane, line ``python3``, on the same clock.

The reduction, per device plane:

- busy: the union of the ``XLA Ops`` intervals;
- window: from the first operation's start to the last one's end, the
  same for every chip;
- time by operation: self time (an event's duration less the events
  nested in it), by instruction name with the trailing ``.<digits>``
  cut off and, for the breakdown, by :func:`row`;
- categories: ``tpu_custom_call``, ``collective`` (by opcode), ``other``;
- exposed collective time: the collective intervals of both lines less
  the intervals in which another instruction runs on the serial line;
- launches: the ``XLA Modules`` events;
- idle gaps: the complement of busy inside the window, each given to
  the benchmark host span that covers most of it.
"""

import gzip
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def load(path):
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return out


def measure(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` outside ``b``."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cursor = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append([cursor, b[k][0]])
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def parse(text):
    """``(name, opcode, result shape)`` of an event named by an HLO
    instruction's text; a plain name comes back as it is."""
    if not text.startswith("%") or " = " not in text:
        return text, text, ""
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):  # a tuple shape: skip to its closing bracket
        depth = 0
        for i, char in enumerate(rest):
            depth += (char == "(") - (char == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return name, rest.split("(", 1)[0], shape


def base_name(name):
    """``fusion.123`` -> ``fusion``: one row for an operation's copies."""
    return re.sub(r"(\.\d+)+$", "", name)


def category(text):
    name, opcode, _ = parse(text)
    # by opcode, or by name where XLA wraps the collective in a fusion
    if COLLECTIVE.match(opcode) or COLLECTIVE.match(name):
        return "collective"
    if PALLAS_TARGET in text:
        return "tpu_custom_call"
    return "other"


def row(text):
    """The breakdown's row for an instruction: its name without the
    number, its opcode where that says more, and the head of its result
    shape.  The 24 layers' copies of one fusion or kernel fall into one
    row, and ``fusion`` alone would say nothing."""
    name, opcode, shape = parse(text)
    short = base_name(name)
    return " ".join(part for part in (
        short, "" if opcode in (name, short) else opcode, shape[:56])
        if part)


def self_times(events):
    """``(name, self_ns)`` for events ``(name, start, end)`` of one line:
    an event's duration less that of the events nested in it."""
    out, stack = [], []  # open events as [name, end, self_ns]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def reduce_planes(planes, span_names=()):
    """``planes``: ``{plane name: {line name: [(event name, start_ns,
    end_ns), ...]}}``.  See the module's text for what comes back."""
    host_spans = sorted(
        (start, end, name)
        for plane, lines in planes.items() if plane.startswith("/host:")
        for events in lines.values() for name, start, end in events
        if name in span_names)
    devices = []
    for plane in sorted(planes):
        match = DEVICE_PLANE.match(plane)
        ops = planes[plane].get(OP_LINE, []) if match else []
        if not ops:
            continue
        both = ops + planes[plane].get(ASYNC_LINE, [])
        # a step repeats its few thousand instructions: sort each once
        kind = {text: category(text) for text in {t for t, _, _ in both}}
        by_op, by_name = {}, {}
        by_category = {"tpu_custom_call": 0.0, "collective": 0.0,
                       "other": 0.0}
        for text, self_ns in self_times(ops):
            by_op[text] = by_op.get(text, 0.0) + self_ns / 1e9
            by_category[kind[text]] += self_ns / 1e9
        for text, seconds in by_op.items():
            name = base_name(parse(text)[0])
            by_name[name] = by_name.get(name, 0.0) + seconds
        collective = union((s, e) for text, s, e in both
                           if kind[text] == "collective")
        compute = union((s, e) for text, s, e in ops
                        if kind[text] != "collective")
        devices.append({
            "id": int(match.group(1)),
            "busy": union((s, e) for _, s, e in ops),
            "op_s": by_op, "name_s": by_name, "category_s": by_category,
            "collective_s": measure(collective) / 1e9,
            "collective_exposed_s": measure(
                subtract(collective, compute)) / 1e9,
            "launches": sorted(
                (s, e, n) for n, s, e in planes[plane].get(MODULE_LINE, [])),
        })
    if not devices:
        return {"devices": [], "busy_s": 0.0, "window_s": 0.0,
                "top_ops": [], "idle_gaps": [], "host_spans": host_spans}
    start = min(d["busy"][0][0] for d in devices)
    end = max(d["busy"][-1][1] for d in devices)
    gaps, totals = {}, {}
    for d in devices:
        d["busy_s"] = measure(d["busy"]) / 1e9
        for gap_start, gap_end in subtract([[start, end]], d["busy"]):
            covering, best = "no_benchmark_span", 0
            for s, e, name in host_spans:
                overlap = min(e, gap_end) - max(s, gap_start)
                # the innermost span wins a tie: it starts later
                if overlap > 0 and overlap >= best:
                    covering, best = name, overlap
            gaps[covering] = gaps.get(covering, 0.0) + (
                gap_end - gap_start) / 1e9 / len(devices)
        for text, seconds in d.pop("op_s").items():
            totals[row(text)] = (totals.get(row(text), 0.0)
                                 + seconds / len(devices))
        del d["busy"]

    def ranked(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])]

    return {
        "devices": devices, "window_s": (end - start) / 1e9,
        # averaged over the chips, like the two tables below
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "top_ops": ranked(totals)[:20],
        "idle_gaps": ranked(gaps), "host_spans": host_spans,
    }


def planes_of(profile):
    """``{plane: {line: [(name, start_ns, end_ns)]}}``.  Lines of one
    name are joined: a host line is named after its thread, and the
    eager plane's dispatcher is a second ``python3``."""
    planes = {}
    for plane in profile.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events)
    return planes


def reduce_file(path, span_names=()):
    return reduce_planes(planes_of(load(path)), span_names)


def describe(path, top=12):
    """Planes, lines and their longest events, for a look by hand."""
    for plane, lines in planes_of(load(path)).items():
        print(f"PLANE {plane!r}")
        for line, events in lines.items():
            total = sum(e - s for _, s, e in events)
            print(f"  LINE {line!r}: {len(events)} events, "
                  f"{total / 1e6:.3f} ms summed")
            by_name = {}
            for name, s, e in events:
                n, t = by_name.get(name, (0, 0))
                by_name[name] = (n + 1, t + e - s)
            for name, (n, t) in sorted(
                    by_name.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"      {t / 1e6:10.3f} ms {n:6d} x {row(name)[:90]}")


if __name__ == "__main__":
    describe(sys.argv[1])
