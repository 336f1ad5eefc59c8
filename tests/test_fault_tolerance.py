"""Chaos tests for the paddle_tpu.fault subsystem (ISSUE PR 1: robustness).

Every recovery path is driven by the SAME fault-injection registry that
production flags expose (FLAGS_fault_inject="name[:count|*],..."):

* save failure -> bounded retry succeeds, checkpoint commits
* torn checkpoint (crash between data write and COMMIT) -> auto-resume
  skips it and loads the latest VALID checkpoint
* corrupted payload -> checksum verification rejects it, resume falls back
* SIGTERM mid-step -> graceful best-effort checkpoint + exit 75
  (EX_TEMPFAIL, the launcher's "relaunch me" code)
* N consecutive non-finite losses -> supervisor aborts with a diagnostic
* launch controller: exponential backoff restarts bounded by --max_restarts,
  restart-requested trainers get PADDLE_CKPT_DIR / PADDLE_RESTART_NUM

Launcher subprocess tests reuse the tiny-pure-python-trainer pattern from
test_launch.py; the multi-process restart-resume test is @pytest.mark.slow.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import fault
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.fault import injection as _inj

LAUNCH = [sys.executable, "-m", "paddle_tpu.distributed.launch"]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_after():
    """No chaos leaks: every test ends with the registry disarmed."""
    yield
    fault.disarm()


def _env():
    e = dict(os.environ)
    e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
    return e


def _state(val=1.0):
    return {"w": paddle.to_tensor(np.full((4,), val, np.float32)),
            "b": paddle.to_tensor(np.arange(3, dtype=np.float32))}


# ---------------------------------------------------------------- injection

class TestInjection:
    def test_spec_grammar_counts(self):
        fault.arm("supervisor.step:2")
        with pytest.raises(fault.InjectedFault):
            _inj.inject("supervisor.step")
        with pytest.raises(fault.InjectedFault):
            _inj.inject("supervisor.step")
        _inj.inject("supervisor.step")  # shots spent: passes through
        assert fault.hits("supervisor.step") == 3

    def test_always_and_disarm(self):
        fault.arm("dataloader.next:*")
        for _ in range(3):
            with pytest.raises(fault.InjectedFault):
                _inj.inject("dataloader.next")
        fault.disarm()
        _inj.inject("dataloader.next")
        assert fault.hits("dataloader.next") == 0  # disarm clears counters

    def test_flag_arming_via_set_flags(self):
        # the production arming surface: plain paddle.set_flags / env
        paddle.set_flags({"FLAGS_fault_inject": "collective.all_reduce"})
        try:
            with pytest.raises(fault.InjectedFault):
                _inj.inject("collective.all_reduce")
            _inj.inject("collective.all_reduce")  # one-shot default
        finally:
            paddle.set_flags({"FLAGS_fault_inject": ""})

    def test_rearm_resets_counters(self):
        fault.arm("supervisor.step:1")
        with pytest.raises(fault.InjectedFault):
            _inj.inject("supervisor.step")
        fault.arm("supervisor.step:1")  # same spec re-armed -> fresh shot
        with pytest.raises(fault.InjectedFault):
            _inj.inject("supervisor.step")

    def test_bad_spec_rejected(self):
        with pytest.raises(ValueError, match="count"):
            fault.arm("checkpoint.save:often")
        fault.disarm()

    def test_builtin_points_registered(self):
        pts = fault.fault_points()
        for name in ("dataloader.next", "collective.all_reduce",
                     "launch.spawn", "supervisor.step", "checkpoint.save",
                     "checkpoint.commit", "checkpoint.load"):
            assert name in pts, f"fault point {name} not registered"

    def test_dataloader_fault_point_wired(self):
        ds = [(np.zeros((2,), np.float32),) for _ in range(4)]
        loader = paddle.io.DataLoader(ds, batch_size=2)
        fault.arm("dataloader.next")
        with pytest.raises(fault.InjectedFault):
            list(loader)
        fault.disarm()
        assert len(list(loader)) == 2  # recovered once disarmed

    def test_collective_fault_point_wired(self):
        from paddle_tpu.distributed import collective
        t = paddle.to_tensor(np.ones((2,), np.float32))
        fault.arm("collective.all_reduce")
        with pytest.raises(fault.InjectedFault):
            collective.all_reduce(t)
        fault.disarm()
        collective.all_reduce(t)


# -------------------------------------------------------------- checkpoints

class TestHardenedCheckpoint:
    def test_atomic_commit_and_roundtrip(self, tmp_path):
        sd = _state(3.0)
        path = ckpt.save_checkpoint(sd, str(tmp_path), step=1)
        assert os.path.basename(path) == "step_1"
        assert os.path.exists(os.path.join(path, ckpt.COMMIT_FILE))
        man = ckpt.read_commit_manifest(path)
        assert man["step"] == 1 and "w" in man["arrays"]
        dst = _state(0.0)
        assert ckpt.load_latest(dst, str(tmp_path)) == 1
        np.testing.assert_allclose(dst["w"].numpy(), np.full((4,), 3.0))

    def test_save_failure_retries_then_succeeds(self, tmp_path):
        fault.arm("checkpoint.save:2")  # first two attempts fail
        path = ckpt.save_checkpoint(_state(), str(tmp_path), step=5,
                                    retries=3, backoff=0.01)
        assert fault.hits("checkpoint.save") == 3  # 2 faults + 1 success
        assert ckpt.find_latest_valid(str(tmp_path)) == (5, path)

    def test_save_retries_exhausted_raises(self, tmp_path):
        fault.arm("checkpoint.save:*")
        with pytest.raises(RuntimeError, match="failed after"):
            ckpt.save_checkpoint(_state(), str(tmp_path), step=5,
                                 retries=2, backoff=0.01)
        fault.disarm()
        assert ckpt.find_latest_valid(str(tmp_path)) is None
        # no stray committed dirs; only .tmp debris at worst
        for d in os.listdir(tmp_path):
            assert not ckpt._STEP_RE.match(d)

    def test_torn_checkpoint_skipped_on_resume(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(_state(1.0), root, step=1)
        # crash between data write and COMMIT: data durable, marker absent
        fault.arm("checkpoint.commit")
        with pytest.raises(fault.InjectedFault):
            ckpt.save_checkpoint(_state(2.0), root, step=2, retries=0)
        fault.disarm()
        assert os.path.isdir(os.path.join(root, "step_2.tmp"))
        assert ckpt.find_latest_valid(root)[0] == 1
        dst = _state(0.0)
        assert ckpt.load_latest(dst, root) == 1
        np.testing.assert_allclose(dst["w"].numpy(), np.full((4,), 1.0))
        # the torn step can be re-saved cleanly over its debris
        ckpt.save_checkpoint(_state(2.0), root, step=2)
        assert ckpt.find_latest_valid(root)[0] == 2

    def test_corrupt_payload_falls_back_to_older(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(_state(1.0), root, step=1)
        p2 = ckpt.save_checkpoint(_state(2.0), root, step=2)
        # flip bytes in step_2's payload without touching its manifest
        corrupted = False
        for dirpath, _, files in os.walk(p2):
            for fn in files:
                if fn == ckpt.COMMIT_FILE:
                    continue
                fp = os.path.join(dirpath, fn)
                if os.path.getsize(fp) > 64:
                    with open(fp, "r+b") as f:
                        f.seek(-32, os.SEEK_END)
                        f.write(b"\xde\xad\xbe\xef" * 8)
                    corrupted = True
        assert corrupted, "found no payload file to corrupt"
        dst = _state(0.0)
        step = ckpt.load_latest(dst, root)
        assert step == 1, "resume must fall back past the corrupt checkpoint"
        np.testing.assert_allclose(dst["w"].numpy(), np.full((4,), 1.0))

    def test_retention_keeps_last_n_and_prunes_tmp(self, tmp_path):
        root = str(tmp_path)
        for s in range(1, 5):
            ckpt.save_checkpoint(_state(float(s)), root, step=s, keep_last_n=2)
        steps = sorted(s for s, _ in ckpt._committed_steps(root))
        assert steps == [3, 4]
        # stale torn debris from an OLD step is swept by the next commit
        os.makedirs(os.path.join(root, "step_1.tmp"), exist_ok=True)
        ckpt.save_checkpoint(_state(5.0), root, step=5, keep_last_n=2)
        assert not os.path.exists(os.path.join(root, "step_1.tmp"))

    def test_load_latest_env_root(self, tmp_path, monkeypatch):
        root = str(tmp_path)
        ckpt.save_checkpoint(_state(7.0), root, step=3)
        monkeypatch.setenv("PADDLE_CKPT_DIR", root)
        dst = _state(0.0)
        assert ckpt.load_latest(dst) == 3  # root from the launcher env
        np.testing.assert_allclose(dst["w"].numpy(), np.full((4,), 7.0))

    def test_load_latest_empty_root_returns_none(self, tmp_path):
        assert ckpt.load_latest(_state(), str(tmp_path)) is None

    def test_verify_checkpoint_detects_mismatch(self, tmp_path):
        root = str(tmp_path)
        path = ckpt.save_checkpoint(_state(1.0), root, step=1)
        good = _state(1.0)
        ckpt.load_state_dict(good, path)
        ckpt.verify_checkpoint(good, path)  # matches: no raise
        bad = {"w": paddle.to_tensor(np.full((4,), 9.0, np.float32)),
               "b": good["b"]}
        with pytest.raises(ckpt.CheckpointCorruption):
            ckpt.verify_checkpoint(bad, path)


# --------------------------------------------------------------- supervisor

class TestSupervisor:
    def test_nan_watchdog_aborts_with_diagnostic(self):
        with fault.Supervisor(max_bad_steps=3, handle_signals=False) as sup:
            sup.after_step(1.0)
            sup.after_step(float("nan"))
            sup.after_step(float("inf"))
            with pytest.raises(fault.NonFiniteLossError,
                               match="3 consecutive"):
                sup.after_step(float("nan"))

    def test_finite_step_resets_consecutive_count(self):
        with fault.Supervisor(max_bad_steps=2, handle_signals=False) as sup:
            for _ in range(5):  # never two in a row
                sup.after_step(float("nan"))
                sup.after_step(0.5)
            assert sup.total_bad_steps == 5 and sup.bad_steps == 0

    def test_scaler_skip_steps_count_as_bad(self):
        """The AMP scaler's found-inf signal (its skip-step machinery) feeds
        the watchdog even when the reported loss itself is finite."""
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0)
        w = paddle.to_tensor(np.ones((2,), np.float32), stop_gradient=False)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])
        with fault.Supervisor(max_bad_steps=2, handle_signals=False) as sup:
            sup.attach_scaler(scaler)
            for i in range(2):
                bad = paddle.to_tensor(np.array([np.inf, 1.0], np.float32))
                loss = (w * bad).sum()
                scaled = scaler.scale(loss)
                scaled.backward()
                scaler.step(opt)   # skipped: grads contain inf
                scaler.update()
                assert scaler.last_found_inf
                opt.clear_grad()
                if i < 1:
                    sup.after_step(1.0)  # finite loss, but scaler skipped
                else:
                    with pytest.raises(fault.NonFiniteLossError):
                        sup.after_step(1.0)

    def test_guard_checkpoints_on_crash(self, tmp_path):
        saved = []
        sup = fault.Supervisor(save_fn=lambda: saved.append(sup.step),
                               handle_signals=False)
        with pytest.raises(ZeroDivisionError):
            with sup.guard():
                1 / 0
        assert saved == [0], "crash inside guard() must best-effort save"

    def test_save_fn_failure_never_masks_the_crash(self):
        def bad_save():
            raise IOError("disk full")
        sup = fault.Supervisor(save_fn=bad_save, handle_signals=False)
        with pytest.raises(ZeroDivisionError):  # NOT IOError
            with sup.guard():
                1 / 0

    def test_request_stop_checkpoints_and_exits_75(self, tmp_path):
        saved = []
        sup = fault.Supervisor(save_fn=lambda: saved.append(True),
                               handle_signals=False)
        sup.after_step(1.0)
        sup.request_stop(signal.SIGTERM)
        with pytest.raises(fault.RestartRequested) as ei:
            sup.after_step(1.0)
        assert ei.value.code == fault.RESTART_EXIT_CODE == 75
        assert saved == [True]

    def test_run_supervised_diverged(self):
        with pytest.raises(fault.NonFiniteLossError):
            fault.run_supervised(lambda i: float("nan"), steps=10,
                                 max_bad_steps=2)

    def test_injected_step_fault_triggers_guard_save(self, tmp_path):
        """FLAGS_fault_inject chaos on the supervisor's own step boundary."""
        saved = []
        sup = fault.Supervisor(save_fn=lambda: saved.append(True),
                               handle_signals=False)
        fault.arm("supervisor.step")
        with pytest.raises(fault.InjectedFault):
            with sup.guard():
                sup.after_step(1.0)
        assert saved == [True]


# -------------------------------------------------- model fit + end-to-end

class TestTrainingIntegration:
    def _model(self):
        from paddle_tpu import nn
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=1e30,  # forces divergence
                                   parameters=net.parameters())
        model.prepare(opt, paddle.nn.MSELoss())
        return model

    def test_fit_aborts_on_diverged_training(self):
        model = self._model()
        data = [(np.random.rand(4).astype(np.float32) * 1e6,
                 np.zeros((2,), np.float32)) for _ in range(32)]
        with pytest.raises(fault.NonFiniteLossError, match="diverged"):
            model.fit(data, batch_size=4, epochs=4, verbose=0,
                      max_bad_steps=3)

    def test_chaos_resume_cycle(self, tmp_path):
        """The acceptance story: train with per-step checkpoints, inject a
        save failure (retried through) then a torn commit (crash), and
        resume from the latest VALID checkpoint."""
        root = str(tmp_path)
        from paddle_tpu import nn
        paddle.seed(0)
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        x = paddle.to_tensor(np.random.RandomState(0)
                             .rand(8, 4).astype(np.float32))

        def step():
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return float(loss.numpy())

        sd = {"net": net.state_dict(), "opt": opt.state_dict()}
        step(); ckpt.save_checkpoint(sd, root, step=1)
        # step 2: transient storage blip — retry commits anyway
        fault.arm("checkpoint.save:1")
        step(); ckpt.save_checkpoint(sd, root, step=2, backoff=0.01)
        w_step2 = net.weight.numpy().copy()
        # step 3: hard crash between data write and COMMIT (torn)
        fault.arm("checkpoint.commit")
        step()
        with pytest.raises(fault.InjectedFault):
            ckpt.save_checkpoint(sd, root, step=3, retries=0)
        fault.disarm()

        # "relaunched" trainer: fresh model resumes from latest VALID
        paddle.seed(123)  # different init — resume must overwrite it
        net2 = nn.Linear(4, 2)
        opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=net2.parameters())
        sd2 = {"net": net2.state_dict(), "opt": opt2.state_dict()}
        resumed = ckpt.load_latest(sd2, root)
        assert resumed == 2, "must skip the torn step_3 checkpoint"
        net2.set_state_dict(sd2["net"])
        opt2.set_state_dict(sd2["opt"])
        np.testing.assert_allclose(net2.weight.numpy(), w_step2, rtol=1e-6)

    def test_sigterm_mid_step_graceful_checkpoint_exit75(self, tmp_path, run_child):
        """SIGTERM a live supervised trainer: it must commit a best-effort
        checkpoint and exit with the restart-requested code (75)."""
        root = tmp_path / "ckpt"
        script = tmp_path / "train.py"
        script.write_text(
            "import os, sys, time\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "from paddle_tpu import fault\n"
            "from paddle_tpu.distributed import checkpoint as ckpt\n"
            f"root = {str(root)!r}\n"
            "sd = {'w': paddle.to_tensor(np.ones(4, np.float32))}\n"
            "sup = fault.Supervisor(max_bad_steps=0)\n"
            "sup.save_fn = lambda: ckpt.save_checkpoint(sd, root, sup.step)\n"
            f"open({str(tmp_path / 'ready')!r}, 'w').write('1')\n"
            "for _ in range(100000):\n"
            "    time.sleep(0.02)\n"
            "    sup.after_step(0.5)\n"
        )
        trainer = run_child([sys.executable, str(script)], env=_env())
        deadline = time.time() + 120
        while not (tmp_path / "ready").exists():
            assert time.time() < deadline, "trainer never came up"
            assert trainer.proc.poll() is None, "trainer died before its first step"
            time.sleep(0.1)
        time.sleep(0.3)  # let it take a few steps
        trainer.send_signal(signal.SIGTERM)
        assert trainer.wait(60) == fault.RESTART_EXIT_CODE
        latest = ckpt.find_latest_valid(str(root))
        assert latest is not None, "no checkpoint committed"
        dst = {"w": paddle.to_tensor(np.zeros(4, np.float32))}
        assert ckpt.load_latest(dst, str(root)) == latest[0]
        np.testing.assert_allclose(dst["w"].numpy(), np.ones(4))


# -------------------------------------------------------- launch supervisor

class TestLaunchRestarts:
    def test_restart_budget_with_backoff(self, tmp_path):
        """An always-crashing trainer is relaunched with exponential backoff
        and given up after --max_restarts; lives = 1 + budget."""
        script = tmp_path / "train.py"
        script.write_text(
            "import os, sys\n"
            "open(os.environ['OUT_DIR'] + '/lives', 'a').write('x')\n"
            "sys.exit(3)\n"
        )
        env = _env()
        env["OUT_DIR"] = str(tmp_path)
        t0 = time.time()
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--max_restarts", "2", "--restart_backoff", "0.2",
                      str(script)],
            env=env, cwd=REPO, timeout=120,
            capture_output=True, text=True,
        )
        elapsed = time.time() - t0
        assert r.returncode != 0
        assert (tmp_path / "lives").read_text() == "xxx", "1 run + 2 restarts"
        # exponential backoff floor: 0.2 + 0.4 between the three lives
        assert elapsed >= 0.6, f"no backoff observed ({elapsed:.2f}s)"

    def test_restart_requested_gets_resume_env(self, tmp_path):
        """Exit 75 (preemption drain) relaunches the trainer with the
        checkpoint root + incarnation number in the env contract."""
        script = tmp_path / "train.py"
        script.write_text(
            "import json, os, sys\n"
            "life = os.environ.get('PADDLE_RESTART_NUM', '')\n"
            "rec = {'ckpt': os.environ.get('PADDLE_CKPT_DIR'), 'life': life}\n"
            "open(os.environ['OUT_DIR'] + '/life.' + life, 'w')"
            ".write(json.dumps(rec))\n"
            "if life == '0':\n"
            "    sys.exit(75)  # restart requested (preemption drain)\n"
        )
        env = _env()
        env["OUT_DIR"] = str(tmp_path)
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--max_restarts", "2", "--restart_backoff", "0.05",
                      "--ckpt_dir", str(tmp_path / "ckpt"), str(script)],
            env=env, cwd=REPO, timeout=120,
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        first = json.loads((tmp_path / "life.0").read_text())
        second = json.loads((tmp_path / "life.1").read_text())
        assert first["ckpt"] == second["ckpt"] == str(tmp_path / "ckpt")
        assert (first["life"], second["life"]) == ("0", "1")

    def test_spawn_fault_injection_recovers(self, tmp_path):
        """Arming launch.spawn via the env flag crashes the first spawn
        inside the controller; the restart budget absorbs it."""
        script = tmp_path / "train.py"
        script.write_text(
            "import os\n"
            "open(os.environ['OUT_DIR'] + '/ran', 'a').write('x')\n"
        )
        env = _env()
        env["OUT_DIR"] = str(tmp_path)
        env["FLAGS_fault_inject"] = "launch.spawn:1"
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--max_restarts", "2", "--restart_backoff", "0.05",
                      str(script)],
            env=env, cwd=REPO, timeout=120,
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "ran").read_text() == "x"

    @pytest.mark.slow
    def test_full_restart_resume_training(self, tmp_path):
        """Multi-process restart e2e: life 0 trains, checkpoints, and exits
        75 mid-run; the relaunched life resumes from the committed
        checkpoint via $PADDLE_CKPT_DIR and finishes all steps."""
        root = tmp_path / "ckpt"
        script = tmp_path / "train.py"
        script.write_text(
            "import os, sys\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "from paddle_tpu import fault, nn\n"
            "from paddle_tpu.distributed import checkpoint as ckpt\n"
            "life = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
            "root = os.environ['PADDLE_CKPT_DIR']\n"
            "paddle.seed(0)\n"
            "net = nn.Linear(4, 2)\n"
            "opt = paddle.optimizer.SGD(learning_rate=0.1,"
            " parameters=net.parameters())\n"
            "sd = {'net': net.state_dict(), 'opt': opt.state_dict()}\n"
            "start = ckpt.load_latest(sd, root) or 0\n"
            "if start:\n"
            "    net.set_state_dict(sd['net'])\n"
            "    opt.set_state_dict(sd['opt'])\n"
            "assert (start == 0) == (life == 0), (start, life)\n"
            "x = paddle.to_tensor(np.random.RandomState(0)"
            ".rand(8, 4).astype(np.float32))\n"
            "sup = fault.Supervisor(max_bad_steps=3)\n"
            "sup.step = start\n"
            "for step in range(start, 6):\n"
            "    with sup.guard():\n"
            "        loss = (net(x) ** 2).mean()\n"
            "        loss.backward(); opt.step(); opt.clear_grad()\n"
            "    sup.after_step(loss)  # deferred: no per-step host sync\n"
            "    sup.drain()  # checkpointing next -> settle the NaN check\n"
            "    sd = {'net': net.state_dict(), 'opt': opt.state_dict()}\n"
            "    ckpt.save_checkpoint(sd, root, step + 1, keep_last_n=3)\n"
            "    if step == 2 and life == 0:\n"
            "        sup.request_stop()  # simulated preemption notice\n"
            "        sup.maybe_exit()\n"
            "out = os.environ['OUT_DIR']\n"
            "open(f'{out}/done.{life}', 'w')"
            ".write(repr(net.weight.numpy().tolist()))\n"
        )
        env = _env()
        env["OUT_DIR"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--max_restarts", "2", "--restart_backoff", "0.1",
                      "--ckpt_dir", str(root), str(script)],
            env=env, cwd=REPO, timeout=300,
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "done.1").exists(), "second life never finished"
        assert not (tmp_path / "done.0").exists(), "life 0 should have exited"
        latest = ckpt.find_latest_valid(str(root))
        assert latest is not None and latest[0] == 6


# --------------------------------------------------- heartbeat (PR 2 tentpole)

from paddle_tpu.fault import heartbeat as hb
from paddle_tpu.fault import watchdog as wd


class TestHeartbeat:
    @pytest.fixture(autouse=True)
    def _no_active_writer(self):
        yield
        hb.reset()

    def test_beat_advances_seq_and_carries_step(self, tmp_path):
        w = hb.HeartbeatWriter(tmp_path, rank=0, interval=0)
        w.beat(step=7)
        got = hb.scan_heartbeats(str(tmp_path))
        assert got[0]["seq"] == 2  # one beat at construction + one manual
        assert got[0]["step"] == 7
        assert got[0]["status"] == hb.STATUS_RUNNING
        assert got[0]["pid"] == os.getpid()

    def test_atomic_writes_leave_no_partial_files(self, tmp_path):
        w = hb.HeartbeatWriter(tmp_path, rank=1, interval=0)
        for _ in range(20):
            w.beat()
        assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]

    def test_abort_marker_and_peer_check(self, tmp_path):
        w = hb.HeartbeatWriter(tmp_path, rank=1, interval=0)
        w.abort("synthetic crash")
        aborts = hb.scan_aborts(str(tmp_path))
        assert aborts[1]["reason"] == "synthetic crash"
        # a rank's OWN marker must not evict it (it is already dying)
        hb.check_peer_abort(str(tmp_path), self_rank=1)
        with pytest.raises(hb.PeerAbort) as ei:
            hb.check_peer_abort(str(tmp_path), self_rank=0)
        assert ei.value.code == fault.RESTART_EXIT_CODE
        assert ei.value.rank == 1

    def test_clear_resets_the_directory(self, tmp_path):
        w = hb.HeartbeatWriter(tmp_path, rank=0, interval=0)
        w.abort("x")
        hb.clear(str(tmp_path))
        assert hb.scan_heartbeats(str(tmp_path)) == {}
        assert hb.scan_aborts(str(tmp_path)) == {}

    def test_maybe_start_env_contract(self, tmp_path, monkeypatch):
        monkeypatch.setenv(hb.ENV_DIR, str(tmp_path))
        monkeypatch.setenv(hb.ENV_RANK, "3")
        monkeypatch.setenv(hb.ENV_INTERVAL, "0")
        w = hb.maybe_start()
        assert w is not None and w.rank == 3
        assert hb.maybe_start() is w, "second start must be idempotent"
        assert 3 in hb.scan_heartbeats(str(tmp_path))

    def test_maybe_start_noop_standalone(self, monkeypatch):
        monkeypatch.delenv(hb.ENV_DIR, raising=False)
        assert hb.maybe_start() is None

    def test_supervisor_step_checks_peers(self, tmp_path, monkeypatch):
        monkeypatch.setenv(hb.ENV_DIR, str(tmp_path))
        monkeypatch.setenv(hb.ENV_RANK, "0")
        monkeypatch.setenv(hb.ENV_INTERVAL, "0")
        sup = fault.Supervisor(handle_signals=False)
        sup.after_step(1.0)  # healthy gang: no raise
        assert hb.scan_heartbeats(str(tmp_path))[0]["step"] == 1
        hb.write_abort("peer crash", rank=1, root=str(tmp_path))
        with pytest.raises(hb.PeerAbort):
            sup.after_step(1.0)


# ----------------------------------------------------- watchdog (PR 2 tentpole)

class TestWatchdog:
    def test_disarmed_is_passthrough(self):
        paddle.set_flags({"FLAGS_collective_timeout_sec": 0.0})
        with wd.arm("test.region"):
            pass
        assert not wd._regions

    def test_callable_action_fires_on_overrun(self):
        fired = []
        w = fault.Watchdog(timeout=0.15,
                           action=lambda region, t: fired.append(region))
        with w.arm("test.slow", context="unit"):
            time.sleep(0.5)
        assert fired == ["test.slow"]
        assert not wd._regions

    def test_raise_action_raises_at_region_exit(self):
        w = fault.Watchdog(timeout=0.15, action="raise")
        with pytest.raises(fault.WatchdogTimeout, match="test.late"):
            with w.arm("test.late"):
                time.sleep(0.5)

    def test_fast_region_never_fires(self):
        fired = []
        w = fault.Watchdog(timeout=5.0, action=lambda *a: fired.append(a))
        for _ in range(3):
            with w.arm("test.fast"):
                pass
        assert fired == [] and not wd._regions

    def test_dump_stacks_contents(self):
        import io
        _inj.record_event("unit", "hello-marker")
        buf = io.StringIO()
        fault.dump_stacks(file=buf, note="unit dump")
        out = buf.getvalue()
        assert "unit dump" in out
        assert "MainThread" in out          # every thread's stack is present
        assert "hello-marker" in out        # recent fault events ride along


class TestHangInjection:
    def test_disarmed_is_noop(self):
        t0 = time.monotonic()
        _inj.inject_hang("collective.hang", hang_sec=5.0)
        assert time.monotonic() - t0 < 1.0

    def test_armed_hang_sleeps_and_counts(self):
        fault.arm("collective.hang:1")
        t0 = time.monotonic()
        _inj.inject_hang("collective.hang", hang_sec=0.3)
        assert time.monotonic() - t0 >= 0.3
        assert fault.hits("collective.hang") == 1
        t0 = time.monotonic()
        _inj.inject_hang("collective.hang", hang_sec=5.0)  # shot spent
        assert time.monotonic() - t0 < 1.0

    def test_flag_controls_hang_duration(self):
        paddle.set_flags({"FLAGS_fault_hang_sec": 0.2})
        try:
            fault.arm("dataloader.hang:1")
            t0 = time.monotonic()
            _inj.inject_hang("dataloader.hang")
            assert time.monotonic() - t0 >= 0.2
        finally:
            paddle.set_flags({"FLAGS_fault_hang_sec": 3600.0})

    def test_hang_points_registered(self):
        pts = fault.fault_points()
        assert "collective.hang" in pts and "dataloader.hang" in pts


# ---------------------------------------- collective timeouts (PR 2 satellite)

class TestCollectiveTimeout:
    def test_wait_timeout_names_op_and_group(self):
        from paddle_tpu.distributed import collective
        t = paddle.to_tensor(np.ones((4,), np.float32))
        task = collective.all_reduce(t)
        fault.arm("collective.hang:1")
        paddle.set_flags({"FLAGS_fault_hang_sec": 3.0})
        try:
            with pytest.raises(TimeoutError, match="all_reduce"):
                task.wait(timeout=0.3)
        finally:
            paddle.set_flags({"FLAGS_fault_hang_sec": 3600.0})

    def test_wait_completes_within_timeout(self):
        from paddle_tpu.distributed import collective
        t = paddle.to_tensor(np.ones((4,), np.float32))
        assert collective.all_reduce(t).wait(timeout=60) is True

    def test_wait_no_timeout_arms_the_watchdog(self):
        from paddle_tpu.distributed import collective
        fired = []
        old_action = wd.default.action
        wd.default.action = lambda region, t: fired.append(region)
        paddle.set_flags({"FLAGS_collective_timeout_sec": 0.2,
                          "FLAGS_fault_hang_sec": 0.6})
        fault.arm("collective.hang:1")
        try:
            t = paddle.to_tensor(np.ones((2,), np.float32))
            collective.all_reduce(t).wait()
            assert fired == ["collective.all_reduce.wait"]
        finally:
            wd.default.action = old_action
            paddle.set_flags({"FLAGS_collective_timeout_sec": 0.0,
                              "FLAGS_fault_hang_sec": 3600.0})

    def test_peer_abort_preempts_the_wait(self, tmp_path, monkeypatch):
        from paddle_tpu.distributed import collective
        monkeypatch.setenv(hb.ENV_DIR, str(tmp_path))
        monkeypatch.setenv(hb.ENV_RANK, "0")
        hb.write_abort("peer died", rank=1, root=str(tmp_path))
        task = collective.all_reduce(paddle.to_tensor(np.ones((2,), np.float32)))
        with pytest.raises(hb.PeerAbort):
            task.wait()


# ------------------------------------- exactly-once data resume (PR 2 tentpole)

class TestDataResume:
    def _ds(self, n=20):
        return paddle.io.TensorDataset(
            [paddle.to_tensor(np.arange(n, dtype=np.float32).reshape(n, 1))]
        )

    @staticmethod
    def _ids(batches):
        return [b[0].numpy()[:, 0].astype(int).tolist() for b in batches]

    def test_sequential_resume_exact_next_batch(self):
        ds = self._ds()
        ref = self._ids(list(paddle.io.DataLoader(ds, batch_size=2)))
        dl = paddle.io.DataLoader(ds, batch_size=2)
        it = iter(dl)
        seen = self._ids([next(it) for _ in range(3)])
        state = dl.state_dict()
        assert state["batches_consumed"] == 3
        dl2 = paddle.io.DataLoader(ds, batch_size=2)  # "relaunched" process
        dl2.set_state_dict(state)
        rest = self._ids(list(dl2))
        assert seen + rest == ref, "no batch may be replayed or skipped"

    def test_shuffled_resume_replays_the_same_order(self):
        ds = self._ds()
        paddle.seed(11)
        ref = self._ids(list(paddle.io.DataLoader(ds, batch_size=2, shuffle=True)))
        paddle.seed(11)
        dl = paddle.io.DataLoader(ds, batch_size=2, shuffle=True)
        it = iter(dl)
        seen = self._ids([next(it) for _ in range(4)])
        state = dl.state_dict()
        paddle.seed(999)  # the restarted process seeds differently...
        dl2 = paddle.io.DataLoader(ds, batch_size=2, shuffle=True)
        dl2.set_state_dict(state)  # ...but the snapshot restores the epoch key
        rest = self._ids(list(dl2))
        assert seen + rest == ref

    def test_threaded_prefetch_counts_consumed_not_produced(self):
        ds = self._ds(16)
        dl = paddle.io.DataLoader(ds, batch_size=2, num_workers=2,
                                  use_shared_memory=False)
        it = iter(dl)
        next(it); next(it)
        time.sleep(0.2)  # let prefetch run ahead of the consumer
        state = dl.state_dict()
        assert state["batches_consumed"] == 2, \
            "state must track the consumer, not the prefetch thread"
        dl2 = paddle.io.DataLoader(ds, batch_size=2)
        dl2.set_state_dict(state)
        assert self._ids(list(dl2)) == self._ids(
            list(paddle.io.DataLoader(ds, batch_size=2)))[2:]

    def test_epoch_rollover_resets_position(self):
        ds = self._ds(8)
        dl = paddle.io.DataLoader(ds, batch_size=2)
        list(dl); list(dl)
        st = dl.state_dict()
        assert st["epoch"] == 2 and st["batches_consumed"] == 0

    def test_iterable_dataset_resume(self):
        class Stream(paddle.io.IterableDataset):
            def __iter__(self):
                return iter(np.arange(12, dtype=np.float32).reshape(12, 1))

        def ids(batches):  # iterable mode collates to a bare tensor batch
            return [np.asarray(b).astype(int)[:, 0].tolist() for b in batches]

        ref = ids(list(paddle.io.DataLoader(Stream(), batch_size=2)))
        dl = paddle.io.DataLoader(Stream(), batch_size=2)
        it = iter(dl)
        seen = ids([next(it) for _ in range(2)])
        dl2 = paddle.io.DataLoader(Stream(), batch_size=2)
        dl2.set_state_dict(dl.state_dict())
        assert seen + ids(list(dl2)) == ref

    def test_distributed_sampler_state_roundtrip(self):
        ds = self._ds(16)
        samp = paddle.io.DistributedBatchSampler(
            ds, batch_size=2, num_replicas=2, rank=0, shuffle=True)
        samp.set_epoch(5)
        dl = paddle.io.DataLoader(ds, batch_sampler=samp)
        state = dl.state_dict()
        assert state["sampler"] == {"epoch": 5}
        samp2 = paddle.io.DistributedBatchSampler(
            ds, batch_size=2, num_replicas=2, rank=0, shuffle=True)
        dl2 = paddle.io.DataLoader(ds, batch_sampler=samp2)
        dl2.set_state_dict(state)
        assert samp2.epoch == 5
        assert [list(b) for b in samp2] == [list(b) for b in samp]

    def test_manifest_carries_data_state(self, tmp_path):
        ds = self._ds(12)
        dl = paddle.io.DataLoader(ds, batch_size=2)
        it = iter(dl)
        next(it); next(it)
        path = ckpt.save_checkpoint(_state(), str(tmp_path), step=4,
                                    data_loader=dl)
        man = ckpt.read_commit_manifest(path)
        assert man["format_version"] == ckpt.MANIFEST_VERSION == 2
        assert man["data_state"]["batches_consumed"] == 2
        dl2 = paddle.io.DataLoader(ds, batch_size=2)
        dst = _state(0.0)
        assert ckpt.load_latest(dst, str(tmp_path), data_loader=dl2) == 4
        ref = self._ids(list(paddle.io.DataLoader(ds, batch_size=2)))
        assert self._ids(list(dl2)) == ref[2:]


# ------------------------------------ manifest back-compat (PR 2 satellite)

class TestManifestCompat:
    def test_v1_manifest_round_trip(self, tmp_path):
        """A PR-1-era COMMIT (no format_version, no data_state) must still
        read as v1 and resume — only without a data position."""
        root = str(tmp_path)
        path = ckpt.save_checkpoint(_state(4.0), root, step=2)
        cf = os.path.join(path, ckpt.COMMIT_FILE)
        with open(cf) as f:
            man = json.load(f)
        man.pop("format_version")
        man.pop("data_state", None)
        with open(cf, "w") as f:
            json.dump(man, f)
        got = ckpt.read_commit_manifest(path)
        assert got["format_version"] == 1
        dl = paddle.io.DataLoader([(np.zeros((2,), np.float32),)
                                   for _ in range(4)], batch_size=2)
        dst = _state(0.0)
        assert ckpt.load_latest(dst, root, data_loader=dl) == 2
        np.testing.assert_allclose(dst["w"].numpy(), np.full((4,), 4.0))
        assert dl._resume_skip == 0, "v1 has no data position to restore"

    def test_newer_version_still_reads(self, tmp_path):
        root = str(tmp_path)
        path = ckpt.save_checkpoint(_state(1.0), root, step=1)
        cf = os.path.join(path, ckpt.COMMIT_FILE)
        with open(cf) as f:
            man = json.load(f)
        man["format_version"] = 99
        with open(cf, "w") as f:
            json.dump(man, f)
        assert ckpt.read_commit_manifest(path)["format_version"] == 99
        dst = _state(0.0)
        assert ckpt.load_latest(dst, root) == 1  # known fields still honored


# ------------------------------------------ cluster fault domain end-to-end

class TestGangRestart:
    @pytest.mark.slow
    def test_collective_hang_watchdog_gang_restart_exact_resume(self, tmp_path):
        """The PR-2 acceptance test: both ranks hang in an injected
        collective.hang, the watchdog detects it within
        FLAGS_collective_timeout_sec and exits 75, the controller
        gang-restarts ALL ranks, and the resumed run consumes the exact
        next batch — no replay, no skip, no manual intervention."""
        script = tmp_path / "train.py"
        script.write_text(
            "import os, sys\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "from paddle_tpu import fault\n"
            "from paddle_tpu.distributed import checkpoint as ckpt\n"
            "from paddle_tpu.distributed import collective as dist\n"
            "rank = os.environ.get('PADDLE_TRAINER_ID', '0')\n"
            "life = int(os.environ.get('PADDLE_RESTART_NUM', '0'))\n"
            "out = os.environ['OUT_DIR']\n"
            "root = os.path.join(out, 'ckpt_rank' + rank)\n"
            "paddle.seed(5)\n"
            "n = 16\n"
            "ds = paddle.io.TensorDataset([paddle.to_tensor("
            "np.arange(n, dtype=np.float32).reshape(n, 1))])\n"
            "dl = paddle.io.DataLoader(ds, batch_size=2, shuffle=True)\n"
            "sd = {'w': paddle.to_tensor(np.ones(4, np.float32))}\n"
            "start = ckpt.load_latest(sd, root, data_loader=dl) or 0\n"
            "step = start\n"
            "for batch in dl:\n"
            "    ids = batch[0].numpy()[:, 0].astype(int).tolist()\n"
            "    step += 1\n"
            "    ckpt.save_checkpoint(sd, root, step, keep_last_n=2,"
            " data_loader=dl)\n"
            "    with open(out + '/consumed.' + rank, 'a') as f:\n"
            "        f.write(' '.join(map(str, ids)) + '\\n')\n"
            "    if life == 0 and step == 4:\n"
            "        fault.arm('collective.hang:1')\n"
            "        t = paddle.to_tensor(np.ones(2, np.float32))\n"
            "        dist.all_reduce(t).wait()  # hangs; watchdog exits 75\n"
            "        raise SystemExit('unreachable: watchdog never fired')\n"
            "open(out + '/done.' + rank + '.' + str(life), 'w')"
            ".write(str(step))\n"
        )
        env = _env()
        env["OUT_DIR"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        # hang "forever" (60s) relative to the 3s watchdog deadline
        env["FLAGS_fault_hang_sec"] = "60"
        env["FLAGS_collective_timeout_sec"] = "3"
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--nproc_per_node", "2",
                      "--max_restarts", "2", "--restart_backoff", "0.1",
                      "--stop_grace", "8", str(script)],
            env=env, cwd=REPO, timeout=540,
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr[-4000:]
        assert "requested a gang restart" in r.stderr
        for rank in ("0", "1"):
            assert (tmp_path / f"done.{rank}.1").exists(), \
                f"rank {rank} life 1 never finished: {r.stderr[-2000:]}"
            assert not (tmp_path / f"done.{rank}.0").exists(), \
                f"rank {rank} life 0 should have died in the hang"
            lines = (tmp_path / f"consumed.{rank}").read_text().splitlines()
            assert len(lines) == 8, f"rank {rank}: {lines}"
            flat = [int(x) for ln in lines for x in ln.split()]
            assert sorted(flat) == list(range(16)), \
                f"rank {rank} replayed or skipped samples: {flat}"

    @pytest.mark.slow
    def test_heartbeat_loss_exhausted_budget_aborts_with_diagnostic(self, tmp_path):
        """A trainer that stops heartbeating with --max_restarts 0: the
        controller must tear the gang down and abort cleanly, naming the
        stale rank — not hang until an external timeout."""
        script = tmp_path / "train.py"
        script.write_text(
            "import json, os, time\n"
            "d = os.environ['PADDLE_HEARTBEAT_DIR']\n"
            "rank = os.environ['PADDLE_TRAINER_ID']\n"
            "p = os.path.join(d, 'hb_' + rank + '.json')\n"
            "for seq in range(1, 4):\n"
            "    tmp = p + '.tmp.w'\n"
            "    with open(tmp, 'w') as f:\n"
            "        json.dump({'seq': seq, 'step': seq, 'status': 'RUNNING',"
            " 'pid': os.getpid()}, f)\n"
            "    os.replace(tmp, p)\n"
            "    time.sleep(0.2)\n"
            "time.sleep(120)  # hung: no more beats\n"
        )
        t0 = time.time()
        r = subprocess.run(
            LAUNCH + ["--log_dir", str(tmp_path / "log"),
                      "--heartbeat_interval", "0.2",
                      "--heartbeat_timeout", "1.5",
                      "--max_restarts", "0", "--stop_grace", "2",
                      str(script)],
            env=_env(), cwd=REPO, timeout=120,
            capture_output=True, text=True,
        )
        elapsed = time.time() - t0
        assert r.returncode == fault.RESTART_EXIT_CODE, (r.returncode, r.stderr)
        assert "heartbeat stale" in r.stderr
        assert "giving up" in r.stderr
        assert elapsed < 60, "controller must not wait out the hung sleep"
