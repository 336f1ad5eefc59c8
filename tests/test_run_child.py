"""`run_child` (conftest.py), the one way a test starts a process: output to
a file, so a child that prints more than a pipe holds cannot block on a
reader that never comes; the whole process group killed at teardown."""

import sys
import time
from pathlib import Path

import pytest

_SLEEPER = "import time; time.sleep(3600)"


def _running(pid):
    try:  # a zombie is gone: this sandbox's init reaps late
        return Path(f"/proc/{pid}/stat").read_text().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


@pytest.fixture
def gone_after():
    """Set up before `run_child`, so torn down after it: the pids a case
    puts here must be gone once `run_child` has cleaned up."""
    pids = []
    yield pids
    deadline = time.time() + 5
    while any(map(_running, pids)) and time.time() < deadline:
        time.sleep(0.05)
    assert not [p for p in pids if _running(p)]


@pytest.mark.parametrize(
    "code, rc",
    [
        # sixteen pipes' worth: with stdout=PIPE and nobody reading, as the
        # launch tests had it, this child never exits
        ("import sys; sys.stdout.write('x' * (1 << 20))", 0),
        # never exits, and has a child of its own in its group
        (f"import subprocess, sys; p = subprocess.Popen([sys.executable, '-c', {_SLEEPER!r}]);"
         f" print(p.pid, flush=True); {_SLEEPER}", None),
    ],
    ids=["writes_1MB_exits_0", "sleeps_forever"],
)
def test_run_child(gone_after, run_child, code, rc):
    child = run_child([sys.executable, "-c", code])
    gone_after.append(child.proc.pid)
    if rc is not None:
        t0 = time.time()
        assert child.wait(10) == rc and time.time() - t0 < 10
        assert child.out.stat().st_size == 1 << 20
        return
    deadline = time.time() + 30
    while not child.tail().strip() and time.time() < deadline:
        time.sleep(0.05)
    grandchild = int(child.tail())
    assert child.proc.poll() is None and _running(grandchild)
    gone_after.append(grandchild)
