"""Process supervision for the launcher.

Capability parity: python/paddle/distributed/launch/controllers/ in the
reference — Controller.run (controller.py), the collective controller's
pod/process management, per-rank log files + watcher (watcher.py), failure
-triggered teardown, and elastic restart (controllers/master.py:73,186 uses
etcd/HTTP; we use env rendezvous + the TCPStore, SURVEY §5).

TPU-native note: on TPU one process per HOST drives all local chips (SPMD),
so ``nproc_per_node`` here spawns host-level workers (PS/RPC actors, data
workers, CPU-mesh tests) — the role the reference's per-GPU workers play.
Every child gets the launcher env contract: PADDLE_TRAINER_ID,
PADDLE_TRAINERS_NUM, PADDLE_MASTER, PADDLE_TRAINER_ENDPOINTS.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import List, Optional


class ProcContext:
    """One supervised rank (reference: launch/job/container.py)."""

    def __init__(self, rank: int, cmd: List[str], env: dict,
                 log_path: Optional[str]):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self._log_f = None

    def start(self):
        if self.log_path:
            os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
            self._log_f = open(self.log_path, "wb", buffering=0)
            out = self._log_f
        else:
            out = None
        try:
            self.proc = subprocess.Popen(
                self.cmd, env=self.env, stdout=out,
                stderr=subprocess.STDOUT if out else None)
        except BaseException:
            self.close()   # Popen failed (bad script, EMFILE): don't leak fd
            raise
        return self

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    @property
    def returncode(self):
        return None if self.proc is None else self.proc.poll()

    def terminate(self, grace: float = 10.0):
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.terminate()
        try:
            self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()

    def close(self):
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None


class LogWatcher:
    """Tails rank-0's log to the launcher's stdout (reference:
    launch/job/status.py + watcher)."""

    def __init__(self, path: str, out=None):
        self.path = path
        self.out = out or sys.stdout
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        pos = 0

        def drain():
            nonlocal pos
            try:
                with open(self.path, "rb") as f:
                    f.seek(pos)
                    chunk = f.read()
                    if chunk:
                        pos += len(chunk)
                        self.out.write(chunk.decode(errors="replace"))
                        self.out.flush()
            except FileNotFoundError:
                pass

        while not self._stop.is_set():
            drain()
            self._stop.wait(0.2)
        drain()   # final drain: the failing rank's last lines (traceback)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2)


def _host_has_tpu() -> bool:
    """TPU device nodes on this host — looked up on the file system so
    the launcher itself never initializes a backend (and takes a chip)."""
    import glob
    return bool(glob.glob("/dev/accel[0-9]*")
                or glob.glob("/dev/vfio/[0-9]*"))


class LocalController:
    """Spawn + supervise N local ranks (reference:
    launch/controllers/collective.py).

    Failure policy: any rank exiting nonzero tears the job down (all peers
    terminated) and ``run`` returns that rank's exit code — a hung fleet is
    worse than a failed one (comm_task_manager discipline).  With
    ``elastic_level >= 1`` the job is relaunched up to ``max_restarts``
    times (reference elastic manager's RESTART decision).

    On a TPU host every rank inherits the whole host's chips, and a chip
    belongs to one process at a time.  With ``helper_cpu_only`` (the
    default) rank 0 is the one process that reaches them — it can drive
    all of them, SPMD — and ranks > 0 are pinned to the CPU
    (``PADDLE_TPU_HELPER_CPU``): they are host-side helpers, not
    trainers.  Without it, several ranks on one TPU host cannot work —
    the second to initialize fails or hangs — so that is refused; giving
    each rank its own chip is not implemented."""

    def __init__(self, script: str, script_args=None, nproc: int = 1,
                 master: Optional[str] = None, log_dir: Optional[str] = None,
                 job_id: str = "default", elastic_level: int = 0,
                 max_restarts: int = 3, watch_rank0: bool = True,
                 helper_cpu_only: bool = True, nnodes: int = 1,
                 node_rank: int = 0):
        self.script = script
        self.script_args = list(script_args or [])
        self.nproc = nproc
        self.nnodes = nnodes
        self.node_rank = node_rank
        self.master = master or f"127.0.0.1:{_free_port()}"
        self.log_dir = log_dir
        self.job_id = job_id
        self.elastic_level = elastic_level
        self.max_restarts = max_restarts
        self.watch_rank0 = watch_rank0 and log_dir is not None
        self.helper_cpu_only = helper_cpu_only
        if not helper_cpu_only and nproc > 1 and _host_has_tpu():
            raise RuntimeError(
                f"{nproc} ranks on one TPU host would each take every "
                f"chip of the host, and a chip belongs to one process: "
                f"run one rank for the host's chips (one process drives "
                f"them all) or keep helper_cpu_only=True so only rank 0 "
                f"reaches them")
        self.procs: List[ProcContext] = []
        self._store = None   # node-rendezvous store (multi-host only)

    def _exchange_endpoints(self, local_eps: List[str]) -> List[str]:
        """Cross-host endpoint exchange over the master TCPStore (reference:
        launch/controllers/master.py:73,186 — the master KV each node
        registers with).  The node-0 launcher hosts the store; every
        launcher publishes its local endpoint list, then reads all nodes'
        lists in node order to assemble the global contract."""
        from ..store import TCPStore
        host, port = self.master.rsplit(":", 1)
        if self._store is None:
            self._store = TCPStore(host, int(port),
                                   is_master=(self.node_rank == 0),
                                   world_size=self.nnodes)
        prefix = f"launch/{self.job_id}"
        self._store.set(f"{prefix}/node/{self.node_rank}",
                        ",".join(local_eps))
        out: List[str] = []
        for node in range(self.nnodes):
            self._store.wait(f"{prefix}/node/{node}", timeout=120.0)
            val = self._store.get(f"{prefix}/node/{node}")
            if isinstance(val, bytes):
                val = val.decode()
            out.extend(val.split(","))
        return out

    def _build(self) -> List[ProcContext]:
        host = "127.0.0.1" if self.nnodes == 1 else _host_ip()
        local_eps = [f"{host}:{_free_port()}" for _ in range(self.nproc)]
        if self.nnodes > 1:
            endpoints = ",".join(self._exchange_endpoints(local_eps))
        else:
            endpoints = ",".join(local_eps)
        world = self.nnodes * self.nproc
        procs = []
        for rank in range(self.nproc):
            # GLOBAL rank/world (multi-host contract: node_rank*nproc +
            # local); the local rank rides PADDLE_LOCAL_RANK like the
            # reference launcher
            global_rank = self.node_rank * self.nproc + rank
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(global_rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_LOCAL_RANK": str(rank),
                "PADDLE_MASTER": self.master,
                "PADDLE_TRAINER_ENDPOINTS": endpoints,
                "PADDLE_JOB_ID": self.job_id,
            })
            if self.nnodes > 1:
                # the node-0 LAUNCHER hosts the master store (reference:
                # controllers/master.py KV service) — trainer rank 0 must
                # connect as a client, not re-bind the port
                env["PADDLE_MASTER_BOUND"] = "1"
            if self.helper_cpu_only and rank > 0:
                # worker ranks beyond 0 are host-level helpers: rank 0
                # is the one process that reaches the host's chips
                # (framework/backend_guard.py)
                env["PADDLE_TPU_HELPER_CPU"] = "1"
            log = os.path.join(self.log_dir, f"workerlog.{rank}") \
                if self.log_dir else None
            cmd = [sys.executable, self.script] + self.script_args
            procs.append(ProcContext(rank, cmd, env, log))
        return procs

    def _watch(self, poll_s: float = 0.2) -> int:
        """Block until all ranks exit (0) or any rank fails (its code)."""
        while True:
            codes = [p.returncode for p in self.procs]
            bad = [(p.rank, c) for p, c in zip(self.procs, codes)
                   if c not in (None, 0)]
            if bad:
                rank, code = bad[0]
                print(f"[launch] rank {rank} exited with code {code}; "
                      f"terminating peers", file=sys.stderr)
                for p in self.procs:
                    p.terminate()
                return code
            if all(c == 0 for c in codes):
                return 0
            time.sleep(poll_s)

    def _start_all(self) -> List[ProcContext]:
        """Start every rank or none: a partial failure (unwritable log dir,
        EMFILE) must not orphan already-running children."""
        procs = self._build()
        started: List[ProcContext] = []
        try:
            for p in procs:
                started.append(p.start())
        except BaseException:
            for p in started:
                p.terminate()
                p.close()
            raise
        return started

    def run(self) -> int:
        try:
            return self._run()
        finally:
            if self._store is not None:
                self._store.close()
                self._store = None

    def _run(self) -> int:
        restarts = 0
        while True:
            self.procs = self._start_all()
            watcher = None
            interrupted = False
            if self.watch_rank0:
                watcher = LogWatcher(
                    os.path.join(self.log_dir, "workerlog.0")).start()
            try:
                code = self._watch()
            except KeyboardInterrupt:
                for p in self.procs:
                    p.terminate()
                code = 128 + signal.SIGINT
                interrupted = True
            finally:
                if watcher:
                    watcher.stop()
                for p in self.procs:
                    p.close()
            if code == 0:
                return 0
            if interrupted:
                return code        # user asked to stop — never auto-restart
            if self.nnodes > 1:
                # cross-host restart needs job-level coordination (every
                # node must re-rendezvous together) — leave it to the
                # cluster scheduler, like the reference's master controller
                return code
            if self.elastic_level >= 1 and restarts < self.max_restarts:
                restarts += 1
                print(f"[launch] elastic restart {restarts}/"
                      f"{self.max_restarts}", file=sys.stderr)
                continue
            return code


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _host_ip() -> str:
    """This host's address as peers can reach it (multi-node endpoints)."""
    import socket
    try:
        # connecting a UDP socket picks the outbound interface, no traffic
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"
