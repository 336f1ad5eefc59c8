"""Launch CLI (reference: python/paddle/distributed/launch/main.py — the
`python -m paddle.distributed.launch` Controller→Job/Pod/Container model with
elastic restart — SURVEY.md §2.2/§5.3).

TPU-native process model: JAX is single-controller per HOST (one process
drives all local chips), so `--nproc_per_node` defaults to 1 and the CLI's
job is the multi-host contract:

- rendezvous: every node controller registers its endpoint in the native
  TCPStore (csrc/tcp_store.cc) hosted by node 0; the membership for each
  epoch is closed by the master and the full endpoint list + the
  jax.distributed coordinator address are exported to trainers via the
  PADDLE_* env contract;
- failure watch: per-node child supervision with restart-in-place
  (single node) or job-level epoch restart (multi node — a restarted
  trainer cannot rejoin a live jax.distributed job, so every node
  relaunches into a fresh coordination epoch);
- elastic: controllers heartbeat monotonic counters into the store; when
  the master sees a peer go stale it bumps the epoch and the surviving
  nodes re-rendezvous — the job continues as long as >= min nodes
  (--nnodes min:max) re-register.  Node 0 hosting the store is the single
  point of failure, as in the reference's etcd-less collective mode;
- trainer liveness (fault.heartbeat): each trainer writes an atomic
  per-rank heartbeat file (seq counter + step + status) into
  $PADDLE_HEARTBEAT_DIR; the controller polls the seq counters and a rank
  that stops advancing for --heartbeat_timeout, or drops an ABORT marker,
  triggers a COORDINATED gang teardown (SIGTERM all -> --stop_grace ->
  SIGKILL) and a gang relaunch of all ranks — charged to --max_restarts
  with the usual backoff — which auto-resumes from
  checkpoint.find_latest_valid via $PADDLE_CKPT_DIR.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

# EX_TEMPFAIL: a trainer exiting with this code ASKS to be relaunched
# (preemption drained via fault.Supervisor) — same restart budget, but
# logged as requested rather than as a crash.  Kept as a literal so the
# controller stays importable without the paddle_tpu runtime.
RESTART_EXIT_CODE = 75


def _cache_has_entries(d):
    """Warm-start detection: does the compile cache dir hold anything yet?"""
    if not d:
        return False
    try:
        for _root, _dirs, files in os.walk(d):
            if files:
                return True
    except OSError:
        pass
    return False


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch distributed training (TPU hosts)",
    )
    p.add_argument("--nnodes", type=str, default="1", help="N or min:max (elastic)")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=int(os.environ.get("PADDLE_TRAINER_ID", 0)))
    p.add_argument("--master", type=str, default=os.environ.get("PADDLE_MASTER", ""))
    p.add_argument("--devices", "--gpus", type=str, default="", dest="devices")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--run_mode", type=str, default="collective")
    p.add_argument(
        "--max_restart", "--max_restarts", type=int, default=3, dest="max_restart",
        help="restart budget: give up after this many relaunches",
    )
    p.add_argument(
        "--restart_backoff", type=float, default=1.0,
        help="initial delay before a relaunch (s), doubled per consecutive restart",
    )
    p.add_argument(
        "--restart_backoff_max", type=float, default=30.0,
        help="cap on the exponential restart backoff (s)",
    )
    p.add_argument(
        "--ckpt_dir", type=str, default=os.environ.get("PADDLE_CKPT_DIR", ""),
        help="checkpoint root exported to trainers as PADDLE_CKPT_DIR; a "
        "relaunched trainer auto-resumes via distributed.checkpoint.load_latest",
    )
    p.add_argument(
        "--compile_cache_dir", type=str,
        default=os.environ.get("PADDLE_COMPILE_CACHE_DIR", ""),
        help="compile cache root that outlives gang teardowns, so relaunched "
        "ranks reload XLA binaries + AOT snapshots instead of recompiling: "
        "exported to trainers as PADDLE_COMPILE_CACHE_DIR (snapshot tier) "
        "and, unless the environment already places jax's cache, as "
        "JAX_COMPILATION_CACHE_DIR",
    )
    p.add_argument(
        "--first_step_timeout", type=float, default=0.0,
        help="gang-restart when a trainer has not finished step 1 within this "
        "many seconds of spawn (0 disables); scaled by --warm_start_factor "
        "when the compile cache already has entries",
    )
    p.add_argument(
        "--warm_start_factor", type=float, default=0.25,
        help="fraction of --first_step_timeout granted on a warm compile "
        "cache (a relaunch that skips compilation must reach step 1 sooner)",
    )
    p.add_argument("--host", type=str, default="")
    p.add_argument("--hb_interval", type=float, default=2.0, help="node-level heartbeat period (s) in the multi-node TCPStore")
    p.add_argument("--hb_timeout", type=float, default=10.0, help="declare a node dead after this many seconds without a heartbeat")
    p.add_argument(
        "--heartbeat_interval", type=float, default=1.0,
        help="trainer heartbeat-file period (s), exported to trainers as "
        "PADDLE_HEARTBEAT_INTERVAL (fault.Supervisor beats automatically)",
    )
    p.add_argument(
        "--heartbeat_timeout", type=float, default=0.0,
        help="gang-restart the job when a trainer's heartbeat file stops "
        "advancing for this many seconds (0 disables; only ranks that have "
        "written at least one heartbeat are watched)",
    )
    p.add_argument(
        "--stop_grace", type=float, default=10.0,
        help="gang teardown: seconds between SIGTERM and SIGKILL",
    )
    p.add_argument("--rdv_grace", type=float, default=2.0, help="extra wait for stragglers after min nodes registered")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class Container:
    """One trainer process (reference: launch/job/container.py)."""

    def __init__(self, rank, world_size, endpoints, script, script_args, log_dir, extra_env=None):
        self.rank = rank
        self.world_size = world_size
        self.endpoints = endpoints
        self.script = script
        self.script_args = script_args
        self.log_dir = log_dir
        self.extra_env = extra_env or {}
        self.proc = None
        self.log_file = None

    def start(self):
        try:  # chaos point: a trainer that dies at spawn (bad image, OOM)
            from ...fault import injection as _inj

            _inj.inject("launch.spawn", context=f"rank {self.rank}")
        except ImportError:
            pass
        env = dict(os.environ)
        env.update(
            PADDLE_TRAINER_ID=str(self.rank),
            PADDLE_TRAINERS_NUM=str(self.world_size),
            PADDLE_TRAINER_ENDPOINTS=",".join(self.endpoints),
            PADDLE_CURRENT_ENDPOINT=self.endpoints[self.rank] if self.rank < len(self.endpoints) else "",
            PADDLE_LOCAL_RANK=str(self.rank),
            PADDLE_RANK_IN_NODE=str(self.rank),
        )
        env.update(self.extra_env)
        os.makedirs(self.log_dir, exist_ok=True)
        self.log_file = open(os.path.join(self.log_dir, f"workerlog.{self.rank}"), "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", self.script] + list(self.script_args),
            env=env,
            stdout=self.log_file if self.rank != 0 else None,
            stderr=subprocess.STDOUT if self.rank != 0 else None,
        )
        return self.proc

    def poll(self):
        return self.proc.poll() if self.proc else None

    def signal_stop(self):
        """First phase of a gang teardown: SIGTERM (lets fault.Supervisor
        drain to a checkpoint); the controller escalates to SIGKILL after
        the shared grace window."""
        if self.proc and self.proc.poll() is None:
            try:
                self.proc.terminate()
            except OSError:
                pass

    def close_log(self):
        if self.log_file:
            self.log_file.close()
            self.log_file = None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.close_log()

    def kill9(self):
        """SIGKILL with no grace (chaos drills: the process vanishes
        mid-request, exactly like an OOM kill or node loss)."""
        if self.proc and self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass

    def restart(self, grace=10.0):
        """Rolling-restart hook (serving router, elastic controller):
        SIGTERM -> wait up to `grace` for a clean drain -> SIGKILL the
        stragglers -> respawn with the same env contract and a fresh log.
        Returns the new Popen; the caller gates re-admission on /healthz."""
        self.signal_stop()
        if self.proc is not None:
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        self.close_log()
        return self.start()


class CollectiveController:
    """Reference: launch/controllers/collective.py watch loop +
    fleet/elastic/manager.py heartbeat/scale behavior (etcd replaced by the
    native TCPStore)."""

    def __init__(self, args):
        self.args = args
        nn = args.nnodes
        if ":" in nn:
            lo, hi = nn.split(":")
            self.min_nodes, self.max_nodes = int(lo), int(hi)
        else:
            self.min_nodes = self.max_nodes = int(nn)
        if self.max_nodes > 1 and args.nproc_per_node > 1:
            # one single-controller JAX process per host is the TPU model;
            # node-level endpoints cannot describe per-trainer ranks
            raise SystemExit(
                "--nproc_per_node > 1 is not supported with --nnodes > 1 "
                "(one controller process drives all of a host's chips)"
            )
        self.node_rank = args.node_rank
        self.containers = []
        self.store = None
        self.epoch = 0
        self.my_host = args.host or "127.0.0.1"
        self._hb_seen = {}  # node_id -> (counter, local time of last change)
        self._restarts = 0  # lives consumed from the restart budget
        # trainer-level (heartbeat-file) liveness for the local gang
        self.hb_dir = os.path.join(args.log_dir, "heartbeat")
        self._trainer_hb = {}  # rank -> (seq, local time of last change)
        # cold-start accounting: when the current gang was spawned, whether
        # the compile cache had entries then, and which ranks reached step 1
        self._spawn_time = time.time()
        self._cache_warm = False
        self._first_step = {}  # rank -> local time of first step>=1 heartbeat

    # -- store / rendezvous ------------------------------------------------
    def _connect_store(self):
        from ...native import TCPStore

        host, port = self.args.master.rsplit(":", 1)
        port = int(port)
        if self.node_rank == 0:
            self.store = TCPStore(host="127.0.0.1", port=port, is_master=True)
        else:
            deadline = time.time() + 60
            last = None
            while time.time() < deadline:
                try:
                    self.store = TCPStore(host=host, port=port)
                    break
                except RuntimeError as e:
                    last = e
                    time.sleep(0.5)
            if self.store is None:
                raise RuntimeError(f"could not reach TCPStore master {host}:{port}: {last}")
        self.coord = f"{host}:{port + 1}"  # jax.distributed coordinator

    def _rendezvous(self, epoch):
        """Register in an epoch; the master closes membership.  A node that
        registers after the close (startup skew, rejoin) bumps to a fresh
        epoch and retries so the whole job converges on one membership.
        Returns (node_epoch_rank, n_nodes, endpoints-by-node)."""
        st = self.store
        while True:
            my_ep = f"{self.my_host}:{_free_port()}"
            rank = st.add(f"ep{epoch}/rank", 1) - 1
            st.set(f"ep{epoch}/node/{rank}", my_ep)
            st.set(f"ep{epoch}/nodeid/{rank}", str(self.node_rank))
            st.add(f"hb/{self.node_rank}", 1)
            if self.node_rank == 0:
                # membership: wait for min nodes, then a grace window up to max
                while st.add(f"ep{epoch}/rank", 0) < self.min_nodes:
                    time.sleep(0.2)
                deadline = time.time() + self.args.rdv_grace
                while time.time() < deadline and st.add(f"ep{epoch}/rank", 0) < self.max_nodes:
                    time.sleep(0.2)
                st.set(f"ep{epoch}/world", str(st.add(f"ep{epoch}/rank", 0)))
            world = int(st.get(f"ep{epoch}/world"))
            if rank >= world:
                # membership closed without us: request a new epoch
                st.set(f"bump/{epoch + 1}", "1")
                epoch += 1
                continue
            eps = [st.get(f"ep{epoch}/node/{i}").decode() for i in range(world)]
            self._member_ids = [int(st.get(f"ep{epoch}/nodeid/{i}")) for i in range(world)]
            self._hb_seen = {}
            self.epoch = epoch
            return rank, world, eps

    # -- spawn -------------------------------------------------------------
    def _spawn(self, node_erank, n_nodes, node_eps):
        args = self.args
        nproc = args.nproc_per_node
        world = n_nodes * nproc
        if n_nodes > 1:
            endpoints = node_eps  # node-level endpoints from the exchange
            extra = {
                "PADDLE_MASTER": self.coord,
                "MASTER_ADDR": self.coord.rsplit(":", 1)[0],
                "PADDLE_RESTART_EPOCH": str(self.epoch),
                "PADDLE_TRAINERS_NUM": str(world),
            }
        else:
            endpoints = [f"127.0.0.1:{_free_port()}" for _ in range(world)]
            extra = {}
        # resume contract: relaunched trainers learn where to look for the
        # newest valid checkpoint and which life they are on
        extra["PADDLE_RESTART_NUM"] = str(self._restarts)
        if args.ckpt_dir:
            extra["PADDLE_CKPT_DIR"] = args.ckpt_dir
        # warm-start contract: the compile cache dir outlives gang teardowns,
        # so a relaunched rank reloads XLA binaries + AOT snapshots instead
        # of recompiling.  FLAGS_* env overrides ride along explicitly — the
        # relaunched gang must run under the SAME flags it crashed under
        # (and the snapshot fingerprint would reject mismatched entries).
        if args.compile_cache_dir:
            extra["PADDLE_COMPILE_CACHE_DIR"] = args.compile_cache_dir
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                extra["JAX_COMPILATION_CACHE_DIR"] = args.compile_cache_dir
        for k, v in os.environ.items():
            if k.startswith("FLAGS_") or k == "PADDLE_COMPILE_CACHE_DIR":
                extra.setdefault(k, v)
        self._cache_warm = _cache_has_entries(args.compile_cache_dir)
        self._spawn_time = time.time()
        self._first_step = {}
        # liveness contract: trainers beat into hb_dir; a fresh gang must
        # never read a dead life's heartbeat/ABORT state
        from ...fault import heartbeat as _hbmod

        os.makedirs(self.hb_dir, exist_ok=True)
        _hbmod.clear(self.hb_dir)
        self._trainer_hb = {}
        extra["PADDLE_HEARTBEAT_DIR"] = self.hb_dir
        extra["PADDLE_HEARTBEAT_INTERVAL"] = str(args.heartbeat_interval)
        # drain contract: a serving rank (inference.serve) turns SIGTERM
        # into drain mode and must finish in-flight requests within the
        # SAME grace the gang teardown allows before SIGKILL
        extra["PADDLE_STOP_GRACE"] = str(args.stop_grace)
        self.containers = []
        for lr in range(nproc):
            grank = node_erank * nproc + lr
            c = Container(
                grank, world, endpoints, args.training_script,
                args.training_script_args, args.log_dir, extra_env=extra,
            )
            c.start()
            self.containers.append(c)

    # -- run ---------------------------------------------------------------
    def run(self):
        args = self.args
        multi = self.max_nodes > 1
        if multi:
            if not args.master:
                raise SystemExit("--master host:port is required when nnodes > 1")
            self._connect_store()
            node_erank, n_nodes, node_eps = self._rendezvous(self.epoch)
        else:
            node_erank, n_nodes, node_eps = 0, 1, []

        restarts = 0
        while True:
            self._restarts = restarts
            try:
                self._spawn(node_erank, n_nodes, node_eps)
                code = self.watch(multi, n_nodes)
            except Exception as e:
                # a failed spawn is supervised like a crashed child: backoff
                # and retry within the same restart budget
                print(f"[launch] spawn failed: {e}", file=sys.stderr)
                code = 1
            self._gang_stop()
            if code == 0:
                return 0
            if code == "interrupt":
                return 130
            if code == "abort":
                return 1
            if code == "epoch":
                # peer died / membership change: everyone re-rendezvouses
                self.epoch += 1
                print(f"[launch] re-rendezvous epoch {self.epoch}", file=sys.stderr)
                try:
                    node_erank, n_nodes, node_eps = self._rendezvous(self.epoch)
                except Exception as e:
                    print(f"[launch] rendezvous failed: {e}", file=sys.stderr)
                    return 1
                continue
            restarts += 1
            if restarts > args.max_restart:
                print(f"[launch] giving up after {restarts - 1} restarts", file=sys.stderr)
                return code
            # exponential backoff: a crash-looping trainer must not hammer
            # the pod (or the rendezvous master) at full speed
            delay = min(
                args.restart_backoff * (2 ** (restarts - 1)),
                args.restart_backoff_max,
            )
            why = (
                "requested a gang restart (exit 75: preemption drain, "
                "watchdog timeout, or health eviction)"
                if code == RESTART_EXIT_CODE
                else f"failed (exit {code})"
            )
            print(
                f"[launch] child {why}; gang restart {restarts}/"
                f"{args.max_restart} in {delay:.1f}s",
                file=sys.stderr,
            )
            try:
                # controller-side flight-recorder dump: the gang is about to
                # be torn down and respawned, so write the event timeline
                # next to the checkpoints the restart will resume from
                from ...obs import flight as _flight

                _flight.record(
                    "launch",
                    f"gang restart {restarts}/{args.max_restart}: {why}",
                    exit_code=code, delay_s=round(delay, 2),
                )
                _flight.dump(f"gang-restart-{restarts}")
            except ImportError:
                pass
            time.sleep(delay)
            if multi:
                # a restarted trainer cannot rejoin a live jax.distributed
                # job: force a job-level epoch restart instead
                self.store.set(f"bump/{self.epoch + 1}", "1")
                self.epoch += 1
                node_erank, n_nodes, node_eps = self._rendezvous(self.epoch)

    # -- gang teardown -----------------------------------------------------
    def _gang_stop(self, grace=None):
        """Coordinated teardown: SIGTERM every trainer FIRST (so all ranks
        drain concurrently — fault.Supervisor turns it into a best-effort
        checkpoint), then one shared grace window, then SIGKILL stragglers.
        A partial teardown would leave surviving ranks deadlocked inside a
        collective against the dead ones."""
        grace = self.args.stop_grace if grace is None else grace
        for c in self.containers:
            c.signal_stop()
        deadline = time.time() + grace
        stragglers = []
        for c in self.containers:
            if c.proc and c.proc.poll() is None:
                try:
                    c.proc.wait(max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    stragglers.append(c)
        for c in stragglers:
            print(
                f"[launch] rank {c.rank} ignored SIGTERM for {grace:.1f}s; killing",
                file=sys.stderr,
            )
            try:
                c.proc.kill()
                c.proc.wait(5)
            except (OSError, subprocess.TimeoutExpired):
                pass
        for c in self.containers:
            c.close_log()

    # -- watch -------------------------------------------------------------
    def _trainer_health(self, now):
        """Trainer-file liveness for the local gang: an ABORT marker or a
        stale heartbeat (seq counter unchanged for --heartbeat_timeout of
        the CONTROLLER's clock — no cross-process clock comparison) turns
        into a gang restart charged to the normal restart budget."""
        from ...fault import heartbeat as _hbmod

        aborts = _hbmod.scan_aborts(self.hb_dir)
        for rank, info in sorted(aborts.items()):
            print(
                f"[launch] rank {rank} dropped ABORT marker "
                f"({info.get('reason', '?')}); gang restart",
                file=sys.stderr,
            )
            return RESTART_EXIT_CODE
        hbs = _hbmod.scan_heartbeats(self.hb_dir)
        # time-to-first-step: the cold-start metric this controller manages.
        # Logged once per rank per gang; the warm/cold tag ties it to the
        # compile cache state at spawn.
        for rank, payload in sorted(hbs.items()):
            step = payload.get("step") or 0
            if rank not in self._first_step and step >= 1:
                self._first_step[rank] = now
                print(
                    f"[launch] rank {rank} time_to_first_step="
                    f"{now - self._spawn_time:.2f}s "
                    f"({'warm' if self._cache_warm else 'cold'} compile cache)",
                    file=sys.stderr,
                )
        if self.args.first_step_timeout > 0:
            deadline = self.args.first_step_timeout * (
                self.args.warm_start_factor if self._cache_warm else 1.0
            )
            if (
                len(self._first_step) < len(self.containers)
                and now - self._spawn_time > deadline
            ):
                missing = [
                    c.rank for c in self.containers
                    if c.rank not in self._first_step
                ]
                print(
                    f"[launch] ranks {missing} did not reach step 1 within "
                    f"{deadline:.1f}s "
                    f"({'warm' if self._cache_warm else 'cold'} deadline); "
                    "gang restart",
                    file=sys.stderr,
                )
                return RESTART_EXIT_CODE
        if self.args.heartbeat_timeout <= 0:
            return None
        for rank, payload in hbs.items():
            seq = payload.get("seq", 0)
            last = self._trainer_hb.get(rank)
            if last is None or seq != last[0]:
                self._trainer_hb[rank] = (seq, now)
            elif now - last[1] > self.args.heartbeat_timeout:
                print(
                    f"[launch] rank {rank} heartbeat stale for "
                    f"{now - last[1]:.1f}s (last step {payload.get('step')}, "
                    f"status {payload.get('status')}, pid {payload.get('pid')}); "
                    "gang restart",
                    file=sys.stderr,
                )
                return RESTART_EXIT_CODE
        return None

    def _heartbeat(self, now):
        st = self.store
        st.add(f"hb/{self.node_rank}", 1)
        if self.node_rank != 0:
            return None
        # master: detect stale peers via monotonic counters (no clock skew)
        for nid in self._member_ids:
            if nid == self.node_rank:
                continue
            cnt = st.add(f"hb/{nid}", 0)  # counters are binary; add(0) reads
            last = self._hb_seen.get(nid)
            if last is None or cnt != last[0]:
                self._hb_seen[nid] = (cnt, now)
            elif now - last[1] > self.args.hb_timeout:
                print(f"[launch] node {nid} heartbeat stale; evicting", file=sys.stderr)
                if len(self._member_ids) - 1 >= self.min_nodes:
                    st.set(f"bump/{self.epoch + 1}", "1")
                    return "epoch"
                print("[launch] below min nodes; aborting", file=sys.stderr)
                return "abort"
        return None

    def watch(self, multi=False, n_nodes=1):
        last_hb = 0.0
        last_health = 0.0
        try:
            while True:
                codes = [c.poll() for c in self.containers]
                if any(c is not None and c != 0 for c in codes):
                    dead = next(
                        (c, rc) for c, rc in zip(self.containers, codes)
                        if rc is not None and rc != 0
                    )
                    print(
                        f"[launch] rank {dead[0].rank} exited {dead[1]}; "
                        "tearing the gang down",
                        file=sys.stderr,
                    )
                    return dead[1]
                if all(c == 0 for c in codes):
                    return 0
                hnow = time.time()
                if hnow - last_health >= min(self.args.heartbeat_interval, 1.0):
                    last_health = hnow
                    verdict = self._trainer_health(hnow)
                    if verdict is not None:
                        return verdict
                if multi:
                    now = time.time()
                    try:
                        if now - last_hb >= self.args.hb_interval:
                            last_hb = now
                            verdict = self._heartbeat(now)
                            if verdict is not None:
                                return verdict
                        if self.store.check(f"bump/{self.epoch + 1}"):
                            return "epoch"
                    except RuntimeError as e:
                        # store connection lost (master exited): stop
                        # supervising rather than running headless forever
                        print(f"[launch] coordination store lost: {e}", file=sys.stderr)
                        return "abort"
                time.sleep(0.2)
        except KeyboardInterrupt:
            return "interrupt"


def main(argv=None):
    args = parse_args(argv)
    ctrl = CollectiveController(args)
    code = ctrl.run()
    sys.exit(code)


if __name__ == "__main__":
    main()
