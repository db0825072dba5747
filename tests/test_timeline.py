"""Timeline tests (reference: test/test_timeline.py — run ops with the
timeline env var set, parse the JSON, assert NEGOTIATE/op events exist).

Run in a subprocess so HVD_TIMELINE is set before init, exactly as the
reference drives it purely via env vars.
"""

import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics

hvd.init()
def fn(r):
    hvd.allreduce(jnp.ones((4,)) * r, name="timeline.tensor", op=hvd.Sum)
    hvd.allgather(jnp.ones((2, 2)), name="timeline.gather")
basics.run_parallel(fn)
hvd.shutdown()
"""


def test_timeline_events(tmp_path):
    timeline_file = tmp_path / "timeline.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_TIMELINE": str(timeline_file),
        "HVD_TIMELINE_MARK_CYCLES": "1",
    })
    result = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                            capture_output=True, text=True, timeout=180,
                            cwd=os.path.dirname(os.path.dirname(__file__)))
    assert result.returncode == 0, result.stderr

    events = json.loads(timeline_file.read_text())
    names = {e.get("name") for e in events}
    assert "NEGOTIATE_ALLREDUCE" in names
    assert "ALLREDUCE" in names
    assert "NEGOTIATE_ALLGATHER" in names
    assert "ALLGATHER" in names
    assert "CYCLE" in names
    # per-tensor pids registered via metadata events
    meta = [e for e in events if e.get("ph") == "M"]
    registered = {e["args"]["name"] for e in meta}
    assert "timeline.tensor" in registered
    assert "timeline.gather" in registered


def test_timeline_well_formed_and_rank_ticks(tmp_path):
    """Beyond event presence: B/E events pair up per tensor track, every
    rank's readiness tick appears during negotiation (reference:
    controller.cc:797-809 per-rank ticks), and timestamps are
    monotonic non-negative."""
    timeline_file = tmp_path / "timeline.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_TIMELINE": str(timeline_file),
    })
    result = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                            capture_output=True, text=True, timeout=180,
                            cwd=os.path.dirname(os.path.dirname(__file__)))
    assert result.returncode == 0, result.stderr
    events = json.loads(timeline_file.read_text())

    # B/E balance per pid (tensor track)
    depth = {}
    for e in events:
        if e.get("ph") == "B":
            depth[e["pid"]] = depth.get(e["pid"], 0) + 1
        elif e.get("ph") == "E":
            depth[e["pid"]] = depth.get(e["pid"], 0) - 1
            assert depth[e["pid"]] >= 0, "E without matching B"
    assert all(d == 0 for d in depth.values()), depth

    # all 8 ranks tick during negotiation (instant events named by rank)
    ticks = {e["name"] for e in events if e.get("ph") == "i"}
    assert {str(r) for r in range(8)} <= ticks, ticks

    # timestamps sane
    ts = [e["ts"] for e in events if "ts" in e]
    assert all(t >= 0 for t in ts)


def test_timeline_disabled_without_env(tmp_path):
    """No HVD_TIMELINE -> no file written anywhere (the subprocess runs
    with an empty tmp dir as cwd so any stray default-path output would
    land there and fail the assert)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("HVD_TIMELINE", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
    })
    result = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                            capture_output=True, text=True, timeout=180,
                            cwd=str(tmp_path))
    assert result.returncode == 0, result.stderr
    assert list(tmp_path.iterdir()) == [], list(tmp_path.iterdir())
