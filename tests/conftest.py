"""Test harness (mirrors the reference's test strategy, SURVEY.md §4):
CPU backend with 8 virtual devices so ALL distributed logic runs with no TPU
(the reference's Gloo/CustomCPU fixture pattern).

A run of the suite owns what it leaves behind.  The process that starts the
run makes one directory for it (xdist workers are told where): jax's compile
cache lives there, so a run neither reads nor grows `<checkout>/.jax_cache`
and does the same work whatever ran in the tree before.  Every test has a
time limit.  A child process is started through `run_child`; whatever else a
test (or the program under it) starts and leaves running is killed when the
test ends, and what outlives a dead worker when the run ends."""

import faulthandler
import hashlib
import os
import re
import shutil
import signal
import subprocess
import tempfile
import warnings
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# xdist starts its workers after this module is imported in the controller:
# they inherit the cache directory and make no run directory of their own
_RUN_DIR = None
if "PYTEST_XDIST_WORKER" not in os.environ:
    _RUN_DIR = tempfile.mkdtemp(prefix="paddle_tpu_tests_")
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(_RUN_DIR, "jax_cache")
    )

import numpy as np
import pytest

# thirteen times the longest tier-1 test; not an option
TEST_LIMIT_S = 300


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["paddle_tpu_run_dir"] = _RUN_DIR


def _run_dir(config):
    return Path(getattr(config, "workerinput", {}).get("paddle_tpu_run_dir") or _RUN_DIR)


def pytest_configure(config):
    if _RUN_DIR is not None:
        # six workers collecting at once would each build csrc/ into the same
        # directory, and the losers skip test_native: build it once, here
        from paddle_tpu import native

        native.get_lib()


_STDERR = pytest.StashKey[int]()
_SEEN = pytest.StashKey[bool]()


def pytest_sessionstart(session):
    # fd 2 as it was before pytest's capture moved it: where a test that
    # passes its limit leaves its stacks
    capman = session.config.pluginmanager.getplugin("capturemanager")
    with capman.global_and_fixture_disabled():
        session.config.stash[_STDERR] = os.dup(2)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    """The time limit, around all of a test (the fixtures of wider scope that
    it is first to set up included).  A test past it ends this process with
    every thread's stack on stderr; xdist names it as failed and goes on in a
    new worker.  `--dist loadfile` hands that worker the dead one's file
    again, the test that ended it included: the marker makes the second
    visit a failure (`pytest_runtest_setup`), not another wait."""
    mark = _run_dir(item.config) / hashlib.sha1(item.nodeid.encode()).hexdigest()
    item.stash[_SEEN] = mark.exists()
    mark.touch()
    faulthandler.dump_traceback_later(
        TEST_LIMIT_S, exit=True, file=item.config.stash[_STDERR]
    )
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        mark.unlink()


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    # xdist queues all of a dead worker's files again, the finished ones too,
    # and a worker handed a finished file never asks for another: late in a
    # run the unfinished file would wait in the queue for ever
    queue = getattr(sched, "workqueue", {})
    for scope in [s for s, tests in queue.items() if all(tests.values())]:
        del queue[scope]


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if item.stash[_SEEN]:
        pytest.fail("ended its worker earlier in this run (time limit or crash)", pytrace=False)


def _descendants(pid):
    out = []
    try:
        tasks = os.listdir(f"/proc/{pid}/task")
    except OSError:
        return out
    for tid in tasks:
        try:
            kids = Path(f"/proc/{pid}/task/{tid}/children").read_text().split()
        except OSError:
            continue
        for kid in map(int, kids):
            out.append(kid)
            out.extend(_descendants(kid))
    return out


def _kill(pids):
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


@pytest.fixture(autouse=True)
def _no_strays(request):
    """Whatever the test, or the program under it, started and left running
    is killed: a forked DataLoader worker that outlives pytest holds pytest's
    stderr, the pipe to `tee`, and the command never returns."""
    before = set(_descendants(os.getpid()))
    yield
    left = set(_descendants(os.getpid())) - before
    if left:
        _kill(left)
        warnings.warn(f"{request.node.nodeid} left processes running, killed: {sorted(left)}")


class Child:
    """A command in a session of its own, its output in a file."""

    def __init__(self, cmd, out, **popen_kw):
        self.out = out
        with open(out, "wb") as f:
            self.proc = subprocess.Popen(
                cmd, stdin=subprocess.DEVNULL, stdout=f, stderr=subprocess.STDOUT,
                start_new_session=True, **popen_kw,
            )

    def wait(self, timeout):
        """The exit code; still running at the deadline fails the test."""
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            pytest.fail(f"{self.proc.args} ran past {timeout} s:\n{self.tail()}", pytrace=False)

    def tail(self, n=4000):
        return self.out.read_bytes()[-n:].decode(errors="replace")

    def send_signal(self, sig):
        """To the whole group: a launch controller's ranks go with it."""
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass


@pytest.fixture
def run_child(tmp_path):
    """`run_child(cmd, **popen_kw)` starts `cmd` and returns its `Child`.
    Pass or fail, teardown kills each child's process group and reaps it; a
    failing test's report shows the tail of each child's output."""
    children = []

    def start(cmd, **popen_kw):
        children.append(Child(cmd, tmp_path / f"child{len(children)}.out", **popen_kw))
        return children[-1]

    yield start
    for c in children:
        c.send_signal(signal.SIGKILL)
        c.proc.wait()
        print(f"--- {c.out} (exit {c.proc.returncode})\n{c.tail()}")


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session):
    _kill(_descendants(os.getpid()))
    if _RUN_DIR is None:
        return
    # a worker ended by the limit skips its teardown and orphans what it
    # started; every process of the run carries the run's directory in its
    # environment (the compile cache's), however it was re-parented
    tag = _RUN_DIR.encode()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            if int(pid) != os.getpid() and tag in Path("/proc", pid, "environ").read_bytes():
                _kill([int(pid)])
        except OSError:
            pass
    shutil.rmtree(_RUN_DIR, ignore_errors=True)


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield
    # amp.decorate activates a persistent dispatch-level AMP state; isolate it
    from paddle_tpu.framework import core as _core

    _core.set_active_amp(None)


# the serving/async suites run under the runtime sanitizer: any unexpected
# trace/compile/host-sync inside a steady-state region is a hard test error
_SANITIZED_MODULES = {
    "test_serving_engine",
    "test_paged_kv",
    "test_serving_fault",
    "test_async_pipeline",
    "test_observability",
    "test_spec_decode",
    "test_lora_serving",
    "test_fused_paged_attention",
    "test_kv_quant",
    "test_tp_serving",
    "test_autoscale_soak",
    "test_disagg_serving",
}


@pytest.fixture(autouse=True)
def _sanitized(request):
    if request.module.__name__ not in _SANITIZED_MODULES:
        yield
        return
    from paddle_tpu.analysis import sanitizer
    from paddle_tpu.framework import core as _core

    _core.set_flags({"FLAGS_debug_sanitize": True})
    sanitizer.reset()
    try:
        yield
        sanitizer.check()
    finally:
        sanitizer.reset()
        _core.set_flags({"FLAGS_debug_sanitize": False})


def finite_difference_grad(fn, x, eps=1e-3):
    """Numeric gradient of scalar fn at numpy array x (OpTest check_grad)."""
    x = np.asarray(x, np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        xp = x.copy()
        xp[idx] += eps
        xm = x.copy()
        xm[idx] -= eps
        g[idx] = (fn(xp.astype(np.float32)) - fn(xm.astype(np.float32))) / (2 * eps)
        it.iternext()
    return g


def paged_engine_steps(eng, bucket):
    """{name: (to_static function, arguments)} of a paged engine's decode
    step and of its fresh prefill at `bucket`, with the arguments `warmup()`
    sends (all-zero tables): for tests that read the traced or compiled
    program and run nothing."""
    from paddle_tpu import to_tensor

    def z(shape, dtype):
        return to_tensor(np.zeros(shape, dtype))

    s, p = eng.slots, eng.pages_per_seq
    return {
        "decode": (eng._decode_fn, (
            z((s, 1), np.int32), z(s, np.int32), z(s, bool), z(s, np.float32),
            eng._poison_zero, eng._key, z((s, p), np.int32), z(s, np.int32))),
        "prefill": (eng._prefill_fn, (
            z((1, bucket), np.int32), z(p, np.int32), to_tensor(np.int32(bucket)),
            to_tensor(np.float32(0.0)), eng._key, z(1, np.int32),
            z((s, 1), np.int32), to_tensor(np.int32(0)))),
    }


def hlo_results(text, shape):
    """[(instruction name, opcode)] of every instruction in an HLO module's
    text, fused computations included, whose result has the dims `shape`."""
    dims = ",".join(str(d) for d in shape)
    pat = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[" + dims + r"\]\S* ([\w\-]+)\(", re.M
    )
    return [(m.group(1), m.group(2)) for m in pat.finditer(text)]
