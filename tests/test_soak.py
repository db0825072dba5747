"""Soak-rig tests (docs/soak.md).

Unit layer (tier-1): the chaos-schedule determinism pins — same seed,
byte-identical spec, including the cross-version contract that old
seeds keep producing the EXACT specs they produced before the
degraded-network cells existed.

Slow layer: bin/hvd-soak itself — the 16-rank chaos soak with every
regression gate, and the 64-rank collect-only scale leg.
"""

import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_soak():
    loader = importlib.machinery.SourceFileLoader(
        "hvd_soak_under_test", os.path.join(REPO, "bin", "hvd-soak"))
    spec = importlib.util.spec_from_loader(loader.name, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


# ---------------------------------------------------- determinism pins ------
def test_generate_spec_old_seed_is_byte_identical():
    """The replay contract across versions: a seed that produced a
    given spec BEFORE the degraded-network cells existed produces the
    byte-identical spec today (degrade cells draw strictly after every
    pre-existing draw)."""
    from horovod_tpu.run.chaos import generate_spec

    # literal pinned from the pre-degrade generator output; a reordered
    # RNG draw (the bug class this guards against) changes these bytes
    want = ("rank0:allgather:1:preempt,rank0:send:5:preempt,"
            "rank3:broadcast:1:preempt")
    assert generate_spec(7, 4, 3, elastic=True) == want
    assert generate_spec(7, 4, 3, elastic=True, degrade=0) == want
    # degrade cells append AFTER the unchanged binary prefix
    with_degrade = generate_spec(7, 4, 3, elastic=True, degrade=2)
    assert with_degrade.startswith(want + ",")
    assert with_degrade == generate_spec(7, 4, 3, elastic=True,
                                         degrade=2)
    # the group-collective cell (ISSUE 14) draws strictly after every
    # pre-existing cell: without --groups the spec is byte-identical
    # to older trees, with it the cell appends after the same prefix
    assert generate_spec(7, 4, 3, elastic=True, groups=False) == want
    with_groups = generate_spec(7, 4, 3, elastic=True, groups=True)
    assert with_groups == want + ",rank3:allreduce:5:crash"
    stacked = generate_spec(7, 4, 3, elastic=True, coord_failover=True,
                            groups=True)
    no_groups = generate_spec(7, 4, 3, elastic=True,
                              coord_failover=True)
    assert stacked.startswith(no_groups + ",")
    # the mid-stream break cells (ISSUE 17) draw strictly after every
    # pre-existing cell: without --blips the spec is byte-identical to
    # older trees, with it the cells append after the same prefix
    assert generate_spec(7, 4, 3, elastic=True, blips=0) == want
    with_blips = generate_spec(7, 4, 3, elastic=True, blips=2)
    assert with_blips.startswith(want + ",")
    assert with_blips == generate_spec(7, 4, 3, elastic=True, blips=2)
    full_stack = generate_spec(7, 4, 3, elastic=True,
                               coord_failover=True, groups=True,
                               blips=1)
    assert full_stack.startswith(stacked + ",")


def test_generate_spec_blip_cells_parse_and_spare_rank0():
    """The mid-stream break cells must land on the link point with a
    reset/blip action on a non-coordinator rank (cutting the
    coordinator's links turns a heal soak into a liveness test)."""
    from horovod_tpu.common import faults
    from horovod_tpu.run.chaos import generate_spec

    for seed in range(8):
        base = generate_spec(seed, 8, 2)
        spec = generate_spec(seed, 8, 2, blips=3)
        assert spec.startswith(base + ",")
        cells = faults.parse_fault_spec(spec[len(base) + 1:])
        assert len(cells) == 3
        for cell in cells:
            assert cell.point == "link"
            assert cell.action in ("reset", "blip")
            assert cell.rank != 0
            if cell.action == "reset":
                assert 0.0 < float(cell.param) <= 1.0
                assert cell.duration is not None and cell.duration > 0


def test_generate_spec_group_cell_parses_and_spares_rank0():
    """The group cell must land on a collective/ring point with a
    crash/drop action on a non-coordinator rank (killing rank 0 turns
    the group-abort cell into a coordinator fail-over test)."""
    from horovod_tpu.common import faults
    from horovod_tpu.run.chaos import generate_spec

    for seed in range(8):
        base = generate_spec(seed, 8, 2)
        spec = generate_spec(seed, 8, 2, groups=True)
        assert spec.startswith(base + ",")
        (cell,) = faults.parse_fault_spec(spec[len(base) + 1:])
        assert cell.point in ("allreduce", "ring")
        assert cell.action in ("crash", "drop")
        assert cell.rank != 0
        assert cell.step >= 2


def test_generate_spec_degrade_cells_parse_and_target_the_link():
    from horovod_tpu.common import faults
    from horovod_tpu.run.chaos import generate_spec

    for seed in range(8):
        specs = faults.parse_fault_spec(
            generate_spec(seed, 8, 2, degrade=3))
        degrade = [s for s in specs if s.point == "link"]
        assert len(degrade) == 3
        for s in degrade:
            assert s.action in ("delay", "jitter", "throttle", "flaky")
            assert s.duration is not None and s.duration > 0


def test_soak_chaos_schedule_is_deterministic_and_rank0_safe():
    soak = _load_soak()
    spec1, cast1 = soak.chaos_spec(11, 16)
    spec2, cast2 = soak.chaos_spec(11, 16)
    assert spec1 == spec2 and cast1 == cast2
    for seed in range(16):
        spec, cast = soak.chaos_spec(seed, 16)
        # rank 0 hosts the coordinator: afflicting it turns the soak's
        # "no false positives" criterion into a guaranteed real abort
        assert 0 not in cast.values()
        # the four base casualties stay distinct; the reset victim must
        # SURVIVE the soak (a healed link on a rank that later dies
        # proves nothing), so it may not be the crash/preempt rank
        base = {cast[k] for k in ("crash", "preempt", "delay", "flaky")}
        assert len(base) == 4
        assert cast["reset"] not in {cast["crash"], cast["preempt"]}
        from horovod_tpu.common import faults
        parsed = faults.parse_fault_spec(spec)
        assert {s.action for s in parsed} == {
            "crash", "preempt", "delay", "flaky", "reset"}


def test_hvd_chaos_cli_exposes_degrade_flag():
    # spec generation itself is pinned above; here only the CLI surface
    # (launching a job from a unit test is the slow tests' business)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    chaos = os.path.join(REPO, "bin", "hvd-chaos")
    out = subprocess.run(
        [sys.executable, chaos, "--help"], env=env,
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0
    assert "--degrade" in out.stdout
    assert "--blips" in out.stdout


# ----------------------------------------------------------- slow legs ------
def _run_soak(args, timeout):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "hvd-soak")] + args,
        env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_soak_16_ranks_all_gates_pass(tmp_path):
    """The acceptance soak: 16 oversubscribed ranks, >=1 crash, >=1
    preemption drain, >=1 delayed link, >=1 flaky link — zero
    false-positive aborts, every reconfiguration within the bound, the
    drained rank exits 0, survivors digest-identical to a chaos-free
    run at the same final membership."""
    proc = _run_soak(["--ranks", "16", "--steps", "8",
                      "--report", str(tmp_path)], timeout=180)
    report_path = tmp_path / "SOAK_r16.json"
    assert report_path.exists(), f"{proc.stdout}\n{proc.stderr}"
    report = json.loads(report_path.read_text())
    assert proc.returncode == 0, (proc.stdout, proc.stderr, report)
    assert report["pass"] is True, report
    assert all(report["gates"].values()), report["gates"]
    assert report["final_size"] == 14, report


@pytest.mark.slow
def test_soak_64_ranks_collect_only_completes(tmp_path):
    """The scale leg: a 64-rank gang forms (rendezvous, secret
    exchange, liveness registration) and tears down clean on one
    oversubscribed host — the O(N) control-plane proof."""
    proc = _run_soak(["--ranks", "64", "--collect-only",
                      "--report", str(tmp_path)], timeout=180)
    report_path = tmp_path / "SOAK_r64.json"
    assert report_path.exists(), f"{proc.stdout}\n{proc.stderr}"
    report = json.loads(report_path.read_text())
    assert proc.returncode == 0, (proc.stdout, proc.stderr, report)
    assert report["pass"] is True, report
    assert report["gates"]["all_ranks_reported"], report
