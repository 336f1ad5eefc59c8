"""Compile-once cold start (ISSUE 3): persistent compilation cache + AOT
executable snapshots + warm gang restarts.

In-process tests cover the snapshot tier's identity/invalidation contract
(jit/cache.py + StaticFunction integration); subprocess round-trips prove
the headline — a FRESH process binds the previous process's artifacts and
pays 0 traces / 0 fresh XLA compiles; the slow chaos drill proves a gang
restart with a warm cache reaches step 1 inside the tightened warm
deadline.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import jit
from paddle_tpu.framework import core as _core
from paddle_tpu.jit import cache as _snap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    e = dict(os.environ)
    e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.pop("PADDLE_COMPILE_CACHE_DIR", None)
    return e


@pytest.fixture
def cache_dir(tmp_path):
    """Route this test's compiles and snapshots through a throwaway root —
    jax's cache where an operator's JAX_COMPILATION_CACHE_DIR would put it,
    the snapshot tier by its flag — and restore both afterwards."""
    import jax

    d = tmp_path / "cc"
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(d))
    paddle.set_flags({"FLAGS_compile_cache_dir": str(d)})
    yield d
    paddle.set_flags({"FLAGS_compile_cache_dir": ""})
    jax.config.update("jax_compilation_cache_dir", prev)


def _make_step():
    paddle.seed(0)
    m = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())

    @jit.to_static
    def step(x, y):
        out = m(x)
        loss = ((out - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return m, step


def _batch(rows=2):
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(rows, 8).astype("float32"))
    y = paddle.to_tensor(rng.rand(rows, 4).astype("float32"))
    return x, y


# ---------------------------------------------------------------------------
# in-process: snapshot identity + invalidation
# ---------------------------------------------------------------------------


class TestSnapshotTier:
    def test_roundtrip_skips_trace(self, cache_dir):
        """A second, identical StaticFunction binds the first one's snapshot:
        trace_count stays 0 and the losses match exactly."""
        x, y = _batch()
        _, step1 = _make_step()
        l1 = [float(step1(x, y).numpy()) for _ in range(3)]
        assert step1.trace_count == 1 and step1.aot_hits == 0

        _, step2 = _make_step()
        l2 = [float(step2(x, y).numpy()) for _ in range(3)]
        assert step2.trace_count == 0, "snapshot should skip the trace"
        assert step2.aot_hits == 1
        np.testing.assert_allclose(l1, l2, rtol=0, atol=0)

    def test_changed_aval_is_clean_miss(self, cache_dir):
        """A different batch shape must NOT bind the stored program."""
        _, step1 = _make_step()
        step1(*_batch(rows=2))
        _, step2 = _make_step()
        step2(*_batch(rows=3))
        assert step2.trace_count == 1 and step2.aot_hits == 0

    def test_version_fingerprint_auto_invalidates(self, cache_dir, monkeypatch):
        """A version bump finds the stale entry and DELETES it instead of
        loading it (satellite: fingerprint mismatch auto-invalidation)."""
        _, step1 = _make_step()
        step1(*_batch())
        files = list((cache_dir / "aot").glob("*.aot"))
        assert len(files) == 1

        monkeypatch.setattr(
            _snap, "_version_salt", lambda: ("paddle-next", "jax-next", "jaxlib-next")
        )
        inv0 = _snap.STATS["invalidated"]
        _, step2 = _make_step()
        step2(*_batch())
        assert step2.trace_count == 1 and step2.aot_hits == 0
        assert _snap.STATS["invalidated"] == inv0 + 1
        # the stale file is gone, replaced by one under the new fingerprint
        remaining = list((cache_dir / "aot").glob("*.aot"))
        assert files[0] not in remaining or len(remaining) == 1

    def test_corrupt_snapshot_falls_back_to_compile(self, cache_dir):
        _, step1 = _make_step()
        l1 = float(step1(*_batch()).numpy())
        (path,) = (cache_dir / "aot").glob("*.aot")
        path.write_bytes(b"not a snapshot")

        corrupt0 = _snap.STATS["corrupt"]
        _, step2 = _make_step()
        l2 = float(step2(*_batch()).numpy())
        assert step2.trace_count == 1 and step2.aot_hits == 0
        assert _snap.STATS["corrupt"] == corrupt0 + 1
        # the corrupt bytes are gone — the fresh trace re-saved a valid
        # entry at the same identity
        assert path.read_bytes() != b"not a snapshot"
        assert l1 == l2

    def test_closure_constants_distinguish_snapshots(self, cache_dir):
        """Two functions with identical source but different closure
        constants (how generation bakes top_k/top_p) must not share a
        snapshot file."""

        def build(scale):
            paddle.seed(0)
            m = nn.Linear(8, 4)

            @jit.to_static
            def fwd(x):
                return (m(x) * scale).mean()

            return fwd

        x, _ = _batch()
        a = build(1.0)
        va = float(a(x).numpy())
        b = build(2.0)
        vb = float(b(x).numpy())
        assert b.aot_hits == 0, "different closure constant must miss"
        assert abs(vb - 2 * va) < 1e-6

    def test_clear_cache_persistent_purges_snapshots(self, cache_dir):
        _, step = _make_step()
        step(*_batch())
        assert list((cache_dir / "aot").glob("*.aot"))
        removed = step.clear_cache(persistent=True)
        assert removed == 1
        assert not list((cache_dir / "aot").glob("*.aot"))
        # default keeps disk entries
        step(*_batch())
        assert step.clear_cache() == 0
        assert list((cache_dir / "aot").glob("*.aot"))

    def test_warmup_compiles_without_executing(self, cache_dir):
        m, step = _make_step()
        w0 = [np.asarray(p.numpy()).copy() for p in m.parameters()]
        x, y = _batch()
        assert jit.warmup([(step, (x, y))]) == 1
        for p, w in zip(m.parameters(), w0):
            np.testing.assert_array_equal(np.asarray(p.numpy()), w)
        entry = next(iter(step._cache.values()))
        assert entry.compiled is not None
        step(x, y)  # dispatches through the precompiled executable
        assert step.trace_count == 1

    def test_warmup_dir_prefetches(self, cache_dir):
        _, step1 = _make_step()
        step1(*_batch())
        assert jit.warmup(str(cache_dir)) == 1
        _, step2 = _make_step()
        step2(*_batch())
        assert step2.aot_hits == 1

    def test_cache_info_shape(self, cache_dir):
        _, step = _make_step()
        step(*_batch())
        info = jit.cache_info()
        assert {"persistent", "aot", "trace", "eager"} <= set(info)
        assert info["persistent"]["dir"] == str(cache_dir)
        assert info["aot"]["saves"] >= 1
        assert info["aot"]["entries"] >= 1
        assert info["aot"]["bytes"] > 0
        report = jit.cache_report()
        assert "aot snapshots" in report and "persistent" in report


# ---------------------------------------------------------------------------
# eager dispatch LRU (satellite)
# ---------------------------------------------------------------------------


class TestEagerLRU:
    def test_flag_bounds_cache(self):
        from paddle_tpu.ops import dispatch as _dispatch

        old = _core.flag("FLAGS_eager_cache_max_entries")
        ev0 = _dispatch._EAGER_STATS["evictions"]
        try:
            paddle.set_flags({"FLAGS_eager_cache_max_entries": 2})
            # distinct shapes -> distinct cache keys
            for n in (1, 2, 3, 4, 5):
                t = paddle.to_tensor(np.ones((n, 3), np.float32))
                (t * 2.0).numpy()
            stats = _dispatch.cache_stats()
            assert stats["entries"] <= 2
            assert stats["capacity"] == 2
            assert stats["evictions"] > ev0
        finally:
            paddle.set_flags({"FLAGS_eager_cache_max_entries": old})

    def test_hits_counted(self):
        from paddle_tpu.ops import dispatch as _dispatch

        t = paddle.to_tensor(np.ones((2, 3), np.float32))
        (t + 1.0).numpy()
        h0 = _dispatch.cache_stats()["hits"]
        (t + 1.0).numpy()
        assert _dispatch.cache_stats()["hits"] > h0


# ---------------------------------------------------------------------------
# flag / env plumbing
# ---------------------------------------------------------------------------


_CACHE_DIR_PROBE = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "import paddle_tpu\n"
    "from paddle_tpu.framework import core\n"
    "import json\n"
    "print('RESULT ' + json.dumps({'before': before,\n"
    "    'after': jax.config.jax_compilation_cache_dir,\n"
    "    'default': core.DEFAULT_COMPILE_CACHE_DIR,\n"
    "    'flag': core.flag('FLAGS_compile_cache_dir')}))\n"
)


def _probe_cache_dir(env):
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_DIR_PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    (line,) = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


class TestFlagPlumbing:
    def test_env_placed_cache_untouched_by_import(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: importing the package leaves
        jax's cache where the environment put it."""
        env = _env()
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
        rec = _probe_cache_dir(env)
        assert rec["before"] == str(tmp_path / "placed")
        assert rec["after"] == str(tmp_path / "placed")

    def test_unplaced_cache_goes_to_fixed_checkout_path(self):
        """No placement from outside: one fixed path under the checkout."""
        rec = _probe_cache_dir(_env())
        assert rec["before"] is None
        assert rec["after"] == os.path.join(REPO, ".jax_cache")
        assert rec["after"] == rec["default"]

    def test_snapshot_flag_does_not_move_jax_cache(self, tmp_path):
        """FLAGS_compile_cache_dir roots the snapshot tier only."""
        import jax

        before = jax.config.jax_compilation_cache_dir
        paddle.set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "viaflag")})
        try:
            assert _snap.snapshot_dir() == str(tmp_path / "viaflag" / "aot")
            assert jax.config.jax_compilation_cache_dir == before
        finally:
            paddle.set_flags({"FLAGS_compile_cache_dir": ""})
        assert _snap.snapshot_dir() is None

    def test_launch_propagates_cache_env(self, tmp_path):
        """Satellite: the controller must hand its cache root (snapshot
        tier and, the environment naming none, jax's cache) and FLAGS_* env
        overrides to (re)launched ranks."""
        script = tmp_path / "train.py"
        script.write_text(
            "import os, json\n"
            "out = {k: os.environ.get(k) for k in"
            " ('PADDLE_COMPILE_CACHE_DIR', 'JAX_COMPILATION_CACHE_DIR',"
            " 'FLAGS_check_nan_inf')}\n"
            "open(os.environ['OUT_FILE'], 'w').write(json.dumps(out))\n"
        )
        env = _env()
        env["OUT_FILE"] = str(tmp_path / "env.json")
        env["FLAGS_check_nan_inf"] = "1"
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--compile_cache_dir", str(tmp_path / "cc"),
             "--log_dir", str(tmp_path / "log"), str(script)],
            env=env, cwd=REPO, timeout=120,
        )
        assert r.returncode == 0
        rec = json.loads((tmp_path / "env.json").read_text())
        assert rec["PADDLE_COMPILE_CACHE_DIR"] == str(tmp_path / "cc")
        assert rec["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cc")
        assert rec["FLAGS_check_nan_inf"] == "1"


# ---------------------------------------------------------------------------
# subprocess round-trips: the headline (fresh process, 0 fresh compiles)
# ---------------------------------------------------------------------------

_TRAIN_SCRIPT = """
import os, sys
os.environ["PADDLE_COMPILE_CACHE_DIR"] = sys.argv[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import jit

paddle.seed(0)
m = nn.Linear(8, 4)
opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())

@jit.to_static
def step(x, y):
    out = m(x)
    loss = ((out - y) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss

rng = np.random.RandomState(0)
x = paddle.to_tensor(rng.rand(2, 8).astype("float32"))
y = paddle.to_tensor(rng.rand(2, 4).astype("float32"))
losses = [float(step(x, y).numpy()) for _ in range(3)]
p = jit.cache_info()["persistent"]
import json
print("RESULT " + json.dumps({
    "traces": step.trace_count, "aot_hits": step.aot_hits,
    "requests": p["requests"], "disk_hits": p["disk_hits"],
    "fresh": p["misses"], "losses": losses,
}))
sys.stdout.flush()
os._exit(0)  # skip XLA teardown (rare benign aborts on exit)
"""

_DECODE_SCRIPT = """
import os, sys
os.environ["PADDLE_COMPILE_CACHE_DIR"] = sys.argv[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import jit
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference import GenerationPredictor

paddle.seed(0)
model = LlamaForCausalLM(LlamaConfig.tiny())
pred = GenerationPredictor(model, max_new_tokens=4)
pred.warmup(batch_size=1, prompt_len=4, max_new_tokens=4)
toks = pred.generate(np.array([[1, 2, 3, 4]], np.int32)).tolist()
fns = model._gen_fns
p = jit.cache_info()["persistent"]
import json
print("RESULT " + json.dumps({
    "traces": sum(f.trace_count for f in fns.values()),
    "aot_hits": sum(f.aot_hits for f in fns.values()),
    "requests": p["requests"], "disk_hits": p["disk_hits"],
    "fresh": p["misses"], "tokens": toks,
}))
sys.stdout.flush()
os._exit(0)
"""


def _run_script(body, cache_dir, tmp_path, name):
    script = tmp_path / name
    script.write_text(body)
    r = subprocess.run(
        [sys.executable, str(script), str(cache_dir)],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, f"no RESULT line (rc={r.returncode}):\n{r.stdout}\n{r.stderr}"
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.slow
def test_second_process_train_step_zero_compiles(tmp_path):
    """Acceptance: a fresh process running an already-cached to_static step
    reports 0 traces and 0 fresh XLA compiles via cache_info()."""
    d = tmp_path / "cc"
    first = _run_script(_TRAIN_SCRIPT, d, tmp_path, "t.py")
    assert first["traces"] == 1 and first["aot_hits"] == 0
    # the AOT-loaded program's HLO differs from the traced one; its compile
    # lands in the persistent cache on run 2, so run 3 is fully warm
    second = _run_script(_TRAIN_SCRIPT, d, tmp_path, "t.py")
    third = _run_script(_TRAIN_SCRIPT, d, tmp_path, "t.py")
    for run in (second, third):
        assert run["traces"] == 0, run
        assert run["aot_hits"] == 1, run
        assert run["losses"] == first["losses"], "cached program must match"
    assert third["fresh"] == 0, f"expected 0 fresh XLA compiles: {third}"
    assert third["requests"] == third["disk_hits"]


@pytest.mark.slow
def test_second_process_decode_zero_compiles(tmp_path):
    """Acceptance: compiled GenerationPredictor decode round-trips the same
    way — fresh process, 0 traces, 0 fresh compiles, identical tokens."""
    d = tmp_path / "cc"
    first = _run_script(_DECODE_SCRIPT, d, tmp_path, "d.py")
    assert first["traces"] == 2  # prompt step + single-token step
    second = _run_script(_DECODE_SCRIPT, d, tmp_path, "d.py")
    third = _run_script(_DECODE_SCRIPT, d, tmp_path, "d.py")
    for run in (second, third):
        assert run["traces"] == 0, run
        assert run["aot_hits"] == 2, run
        assert run["tokens"] == first["tokens"]
    assert third["fresh"] == 0, f"expected 0 fresh XLA compiles: {third}"


# ---------------------------------------------------------------------------
# chaos: warm gang restart resumes within the tightened first-step deadline
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_warm_gang_restart_bounded_first_step(tmp_path):
    """The trainer 'compiles' slowly when the cache dir is empty and fast
    when its warm marker exists (a pure-python proxy for the XLA bill),
    crashes once after step 2, and the relaunched gang must log a WARM
    time_to_first_step that beats the warm deadline (cold would not)."""
    cc = tmp_path / "cc"
    cc.mkdir()
    script = tmp_path / "train.py"
    script.write_text(
        "import json, os, time, sys\n"
        "cc = os.environ['PADDLE_COMPILE_CACHE_DIR']\n"
        "hb = os.environ['PADDLE_HEARTBEAT_DIR']\n"
        "rank = os.environ['PADDLE_TRAINER_ID']\n"
        "life = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
        "marker = os.path.join(cc, 'warm.marker')\n"
        "time.sleep(0.2 if os.path.exists(marker) else 3.0)  # the compile\n"
        "open(marker, 'w').write('1')\n"
        "def beat(seq, step):\n"
        "    p = os.path.join(hb, f'hb_{rank}.json')\n"
        "    tmp = p + f'.tmp.{os.getpid()}'\n"
        "    payload = {'seq': seq, 'mono': time.monotonic(), 'time': time.time(),\n"
        "               'step': step, 'status': 'train', 'pid': os.getpid()}\n"
        "    open(tmp, 'w').write(json.dumps(payload))\n"
        "    os.replace(tmp, p)\n"
        "for step in range(1, 5):\n"
        "    beat(step, step)\n"
        "    time.sleep(0.6)  # stay alive across controller health polls\n"
        "    if step == 2 and life == 0:\n"
        "        sys.exit(75)  # ask for a gang restart\n"
    )
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--compile_cache_dir", str(cc),
         "--first_step_timeout", "30", "--warm_start_factor", "0.1",
         "--restart_backoff", "0.1", "--max_restart", "2",
         "--log_dir", str(tmp_path / "log"), str(script)],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    elapsed = time.time() - t0
    assert r.returncode == 0, r.stderr
    logs = r.stderr
    assert "time_to_first_step" in logs
    assert "(cold compile cache)" in logs, logs
    assert "(warm compile cache)" in logs, logs
    # warm relaunch: 0.2s "compile" + poll cadence, inside the 3s warm
    # deadline (30 * 0.1) that the cold 3s start would have missed
    warm_lines = [ln for ln in logs.splitlines()
                  if "time_to_first_step" in ln and "warm" in ln]
    warm_t = float(warm_lines[0].split("time_to_first_step=")[1].split("s")[0])
    cold_lines = [ln for ln in logs.splitlines()
                  if "time_to_first_step" in ln and "cold" in ln]
    cold_t = float(cold_lines[0].split("time_to_first_step=")[1].split("s")[0])
    assert warm_t < 3.0, logs
    assert warm_t < cold_t, logs
    assert elapsed < 60
