"""Cuts a recorded ``.xplane.pb`` down to a fixture the tests can keep.

    python tests/benchmark/cut_trace.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> <launches>

Keeps the device planes and the host plane's ``python3`` line (where
the benchmark's spans are), on the device planes the lines the
reduction reads, the events up to the end of the first ``<launches>``
program launches, and of every event only its name, start and duration:
statistics, display names and the metadata of events that were cut go.
Nothing is renamed or re-timed.  The file is protobuf (tsl's
``xplane.proto``); this reads and writes its wire format directly, so
that nothing but the standard library is needed.
"""

import gzip
import re
import sys

KEEP_PLANE = re.compile(r"^(/device:TPU:\d+|/host:CPU)$")
KEEP_LINES = {"XLA Ops", "XLA Modules", "Async XLA Ops", "python3"}
LAUNCH_LINE = "XLA Modules"


def varint(data, at):
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def fields(data):
    """``(number, wire type, value)`` of every field of a message."""
    at = 0
    while at < len(data):
        key, at = varint(data, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = varint(data, at)
        elif wire == 2:
            size, at = varint(data, at)
            value, at = data[at:at + size], at + size
        elif wire == 1:
            value, at = data[at:at + 8], at + 8
        elif wire == 5:
            value, at = data[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def encode_varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def field(number, wire, value):
    head = encode_varint(number << 3 | wire)
    if wire == 0:
        return head + encode_varint(value)
    if wire == 2:
        return head + encode_varint(len(value)) + value
    return head + value


def message(parts):
    return b"".join(field(*part) for part in parts)


def first(data, number, default=None):
    return next((v for n, _, v in fields(data) if n == number), default)


def cut_line(line, cutoff_ns):
    """The line without statistics and without events that start after
    ``cutoff_ns``; also the metadata ids still in use."""
    start_ns = first(line, 3, 0)
    kept, used = [], set()
    for number, wire, value in fields(line):
        if number != 4:
            kept.append((number, wire, value))
            continue
        offset_ps = first(value, 2, 0)
        if cutoff_ns is not None and start_ns + offset_ps / 1e3 > cutoff_ns:
            continue
        used.add(first(value, 1))
        kept.append((4, 2, message(
            p for p in fields(value) if p[0] in (1, 2, 3))))
    return message(kept), used


def cut_plane(plane, launches):
    lines = [v for n, _, v in fields(plane) if n == 3
             and first(v, 2, b"").decode() in KEEP_LINES]
    cutoff_ns = None
    for line in lines:
        if first(line, 2).decode() == LAUNCH_LINE:
            ends = sorted(first(line, 3, 0)
                          + (first(e, 2, 0) + first(e, 3, 0)) / 1e3
                          for n, _, e in fields(line) if n == 4)
            cutoff_ns = ends[min(launches, len(ends)) - 1]
    parts, used = [], set()
    for line in lines:
        cut, ids = cut_line(line, cutoff_ns)
        parts.append((3, 2, cut))
        used |= ids
    for number, wire, value in fields(plane):
        if number in (1, 2):
            parts.append((number, wire, value))
        elif number == 4 and first(value, 1) in used:  # a map entry
            metadata = first(value, 2)
            parts.append((4, 2, message([
                (1, 0, first(value, 1)),
                (2, 2, message(p for p in fields(metadata)
                               if p[0] in (1, 2)))])))
    return message(parts)


def cut(data, launches):
    return message(
        (1, 2, cut_plane(plane, launches))
        for number, _, plane in fields(data)
        if number == 1 and KEEP_PLANE.match(first(plane, 2, b"").decode()))


if __name__ == "__main__":
    source, target, count = sys.argv[1], sys.argv[2], int(sys.argv[3])
    opener = gzip.open if source.endswith(".gz") else open
    with opener(source, "rb") as f:
        small = cut(f.read(), count)
    with gzip.open(target, "wb") as f:
        f.write(small)
    print(f"{target}: {len(small)} bytes before gzip")
