"""The suite's own guards (``tests/conftest.py``): the one limit on a
test, that no test waits for a child longer than it, that a rank
under ``spawn_tcp_ranks`` can say as much as it likes, and that the
session's runtime outlives a test that shuts it down."""

import os
import re
import signal
import time

import pytest

import conftest

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_a_test_past_the_limit_fails_by_name(monkeypatch):
    """A body that sleeps past a limit of two seconds fails there, with
    its name and the limit, and no alarm is left pending."""
    monkeypatch.setattr(conftest, "LIMIT", 2)
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"sleeper ran past the suite's limit of 2 s"):
        # (the alarm of this test's own fixture is replaced, and both
        # are cleared on the way out)
        with conftest.time_limit("sleeper"):
            time.sleep(30)
    assert time.monotonic() - began < 10
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is not signal.SIG_DFL


def test_no_test_waits_for_a_child_longer_than_the_limit():
    """Every ``timeout=`` written in a test file is under ``LIMIT``: a
    child that hangs is reaped by the call that started it, with its
    output in the message, before the alarm fires.  (``tests/benchmark``
    belongs to the benchmark's own files.)"""
    over = []
    for name in sorted(os.listdir(TESTS)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(TESTS, name)) as f:
            for n, line in enumerate(f, 1):
                for seconds in re.findall(r"\btimeout ?= ?(\d+)", line):
                    if int(seconds) >= conftest.LIMIT:
                        over.append(f"{name}:{n}: {line.strip()}")
    assert not over, "\n".join(over)


CHATTY_RANK = r"""
import os, sys, time
flag = os.path.join(os.path.dirname(os.path.abspath(__file__)), "said")
if os.environ["HVD_RANK"] == "1":
    for _ in range(64):
        sys.stderr.write("x" * 4095 + "\n")   # 256 KiB: four pipes' worth
    sys.stderr.flush()
    open(flag, "w").close()
else:
    while not os.path.exists(flag):
        time.sleep(0.05)
print("rank", os.environ["HVD_RANK"], "DONE", flush=True)
"""


def test_a_rank_that_says_much_does_not_block_on_the_harness():
    """Rank 0 ends only after rank 1 has written more than a pipe holds:
    with the ranks' output in pipes that are read one rank after the
    other, rank 1 would block in ``write`` and both would hang."""
    results = conftest.spawn_tcp_ranks(2, CHATTY_RANK, timeout=30)
    for rank, (code, out, err) in enumerate(results):
        assert code == 0 and f"rank {rank} DONE" in out, (code, out)
    assert len(results[1][2]) >= 64 * 4096 - 1


def test_a_test_may_shut_the_runtime_down(hvd):
    hvd.shutdown()
    assert not hvd.is_initialized()


def test_and_the_next_test_of_the_worker_finds_it_up(hvd):
    """The session's ``hvd`` is handed out once; after a test that shut
    it down the fixture has initialised it again."""
    assert hvd.is_initialized() and hvd.size() == 8
