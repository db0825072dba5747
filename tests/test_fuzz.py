"""Tier-1 gate for ``bin/hvd-fuzz`` (docs/fuzzing.md).

Four proofs, mirroring tests/test_lint.py's shape for the fuzz gate:

1. every invariant oracle FIRES on a seeded bug (a monkeypatched buggy
   parser) and stays SILENT on the real tree;
2. the distilled regression corpus under tests/fuzz_corpus/ replays
   green — a finding here means a fixed parser bug regressed;
3. the determinism contract holds: the same ``--seed``/``--iters``
   produce a byte-identical report across two separate processes;
4. the CLI contract matches the hvd-lint family (exit codes 0/1/2,
   ``--format json``, ``.hvd-fuzz-baseline.json`` checked in EMPTY —
   bugs get fixed and pinned, never suppressed).
"""

import json
import os
import pickle
import subprocess
import sys

import pytest

from horovod_tpu.checkpoint import manager
from horovod_tpu.common import faults
from horovod_tpu.run import config_parser
from horovod_tpu.run.service import network
from horovod_tpu.tools.fuzz import cli, engine
from horovod_tpu.tools.fuzz.targets import (ALL_TARGETS, checkpoint,
                                            config_yaml, faultspec,
                                            framed)
from horovod_tpu.tools.fuzz.targets import session as session_target

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HVD_FUZZ = os.path.join(REPO, "bin", "hvd-fuzz")


# ----------------------------------------------------------- baseline gate --
def test_baseline_checked_in_and_empty():
    with open(os.path.join(REPO, ".hvd-fuzz-baseline.json")) as fh:
        data = json.load(fh)
    assert data == {"suppressions": []}, (
        "the fuzz baseline must stay empty: fix the parser and pin the "
        "reproducer in tests/fuzz_corpus/ instead of suppressing")


# ------------------------------------------------------------ corpus replay --
def test_corpus_replays_green_and_covers_every_target():
    entries = engine.load_corpus_entries(cli.DEFAULT_CORPUS)
    assert {target for _, target, _, _ in entries} == set(ALL_TARGETS), \
        "every fuzz target needs at least one distilled corpus entry"
    stats, findings, count = cli.run_fuzz(corpus_only=True)
    assert stats == []
    assert count == len(entries) and count >= 15
    assert findings == [], [f.render() for f in findings]


# ----------------------------------------------- full run, silent + steered --
def test_small_fuzz_run_is_clean_and_covers_arcs():
    stats, findings, _ = cli.run_fuzz(seed=3, iters=60)
    assert findings == [], [f.render() for f in findings]
    assert [s["target"] for s in stats] == sorted(ALL_TARGETS)
    for s in stats:
        # coverage steering is alive: the tracer saw real parser arcs
        # and at least the seed corpus survived distillation
        assert s["arcs"] > 0, s
        assert s["corpus"] >= s["corpus_seed"] > 0, s


# -------------------------------------------------------------- determinism --
def _run_cli(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="random")
    return subprocess.run(
        [sys.executable, HVD_FUZZ, *argv],
        capture_output=True, cwd=REPO, env=env, timeout=180)


def test_report_byte_identical_across_processes():
    first = _run_cli("--seed", "7", "--iters", "60")
    second = _run_cli("--seed", "7", "--iters", "60")
    assert first.returncode == 0, first.stdout.decode() + \
        first.stderr.decode()
    assert second.returncode == 0
    assert first.stdout and first.stdout == second.stdout, (
        "same --seed/--iters must produce a byte-identical report "
        "(PYTHONHASHSEED randomized in both runs)")


# ------------------------------------------------------------- CLI contract --
def test_json_format_and_exit_zero(capsys):
    rc = cli.main(["--corpus-only", "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 0
    payload = json.loads(out)
    assert payload["findings"] == []
    assert payload["stats"] == []
    assert payload["corpus_replayed"] >= 15
    assert payload["stale_baseline_keys"] == []


def test_unknown_target_is_usage_error():
    with pytest.raises(SystemExit) as excinfo:
        cli.main(["--targets", "nonsense"])
    assert excinfo.value.code == 2


def test_seeded_bug_is_exit_one_with_rendered_finding(monkeypatch,
                                                      capsys):
    def buggy(sock, key, direction):
        raise KeyError("seeded bug")

    monkeypatch.setattr(network, "read_message", buggy)
    rc = cli.main(["--targets", "framed", "--seed", "1", "--iters", "5",
                   "--no-baseline", "--corpus",
                   os.path.join(REPO, "no-such-corpus")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[fuzz-framed] malformed frame escaped as KeyError" in out
    assert "hvd-fuzz: 1 finding(s)" in out


# ------------------------------------------- oracles fire on seeded bugs --
def test_typed_rejection_oracle(monkeypatch):
    entry = framed.signed_frame(b"not a pickle")
    assert framed.wire_execute(entry) is None  # silent on the real tree

    def buggy(sock, key, direction):
        raise KeyError("seeded bug")

    monkeypatch.setattr(network, "read_message", buggy)
    violation = framed.wire_execute(entry)
    assert violation is not None
    assert violation[0] == "untyped-rejection:KeyError"


def test_unpickle_before_verify_oracle(monkeypatch):
    blob = pickle.dumps(("q", None))

    def sloppy(sock, key, direction):
        # a parser that unpickles without consulting the HMAC first
        return network.pickle.loads(blob)

    monkeypatch.setattr(network, "read_message", sloppy)
    violation = framed.wire_execute(framed.signed_frame(b""))
    assert violation is not None
    assert violation[0] == "unpickle-before-verify"


def test_unbounded_read_oracle(monkeypatch):
    def greedy(sock, key, direction):
        # trusts a (fictional) length field beyond the allocation cap
        sock.recv(engine.ALLOC_CAP + 1)
        raise EOFError

    monkeypatch.setattr(network, "read_message", greedy)
    violation = framed.wire_execute(b"\x00" * 8)
    assert violation is not None
    assert violation[0] == "unbounded-read"


def test_never_process_death_oracle():
    class Dying(engine.FuzzTarget):
        name = "dying"
        path = "x"

        def execute(self, entry):
            raise SystemExit(3)

    violation = engine.guard_execute(Dying(), b"")
    assert violation is not None
    assert violation[0] == "process-exit"


def test_session_liveness_oracle(monkeypatch):
    target = session_target.Target()
    target.setup()
    try:
        assert target._probe_liveness() is None  # real service: alive

        def deaf(self, sock, lock, req, addr):
            return None  # swallows the hello: no welcome, no response

        monkeypatch.setattr(network.MuxService, "_session_serve", deaf)
        violation = target._probe_liveness()
        assert violation is not None
        assert violation[0] == "liveness-lost"
    finally:
        target.teardown()


def test_faultspec_roundtrip_oracle(monkeypatch):
    target = faultspec.Target()
    target.setup()
    try:
        spec = "rank1:allreduce:2:crash"
        assert target.execute(spec) is None

        monkeypatch.setattr(faults.FaultSpec, "__repr__",
                            lambda self: "<garbage spec>")
        violation = target.execute(spec)
        assert violation is not None
        assert violation[0].startswith("repr-not")
    finally:
        target.teardown()


def test_checkpoint_partial_world_oracle(monkeypatch):
    target = checkpoint.Target()
    target.setup()
    try:
        deletion = {"file": "shard1", "data": None}
        assert target.execute(deletion) is None  # real code falls back

        monkeypatch.setattr(
            manager.CheckpointManager, "restore_latest",
            lambda self, state: (checkpoint.STEP, checkpoint.EPOCH))
        violation = target.execute(deletion)
        assert violation is not None
        assert violation[0] == "partial-world-load"
    finally:
        target.teardown()


def test_config_shape_oracle(monkeypatch):
    target = config_yaml.Target()
    target.setup()
    try:
        doc = "fuzz:\n  seed: 3\n"
        assert target.execute(doc) is None

        monkeypatch.setattr(config_parser, "load_config_file",
                            lambda path: ["not", "a", "dict"])
        violation = target.execute(doc)
        assert violation is not None
        assert violation[0] == "config-shape"
    finally:
        target.teardown()


def test_config_untyped_rejection_oracle(monkeypatch):
    target = config_yaml.Target()
    target.setup()
    try:
        def buggy(path):
            raise AttributeError("seeded bug")

        monkeypatch.setattr(config_parser, "load_config_file", buggy)
        violation = target.execute("key: value\n")
        assert violation is not None
        assert violation[0] == "untyped-rejection:AttributeError"
    finally:
        target.teardown()
