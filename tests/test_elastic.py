"""Elastic/fault-tolerance tests: membership over the store, relaunch loop,
watchdog timeout detection, preemption checkpoint-resume (mirrors the
reference's mocked-etcd elastic tests, SURVEY §5)."""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.distributed.fleet.elastic import (
    ElasticManager, ElasticStatus, ElasticController, ELASTIC_EXIT_CODE,
    launch_elastic,
)
from paddle_tpu.distributed.watchdog import (
    CommTaskManager, comm_guard, enable_comm_watchdog,
    disable_comm_watchdog,
)
from paddle_tpu.distributed.fault_tolerance import (
    PreemptionHandler, save_checkpoint, latest_checkpoint, load_checkpoint,
    run_with_resume,
)


@pytest.fixture()
def store():
    s = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
    yield s
    s.close()


class TestElasticManager:
    def test_register_and_hold(self, store):
        m = ElasticManager(store, np=1, host="node-a", ttl=5)
        m.register()
        assert m.alive_nodes() == ["node-a"]
        assert m.watch() == ElasticStatus.HOLD
        m.exit(completed=True)

    def test_membership_change_restart_and_exit(self, store):
        a = ElasticManager(store, np=2, min_np=1, host="na", ttl=5)
        b = ElasticManager(store, np=2, min_np=1, host="nb", ttl=5)
        a.register()
        b.register()
        assert sorted(a.alive_nodes()) == ["na", "nb"]
        assert a.watch() == ElasticStatus.HOLD
        b.deregister()                       # node lost
        assert a.watch() == ElasticStatus.RESTART
        a.min_np = 2
        assert a.watch() == ElasticStatus.EXIT
        a.deregister()

    def test_heartbeat_expiry(self, store):
        m = ElasticManager(store, np=1, host="nc", ttl=0.2,
                           heartbeat_interval=10)   # won't refresh in time
        m.register()
        time.sleep(0.4)
        assert m.alive_nodes() == []
        m.deregister()

    def test_concurrent_registration_atomic(self, store):
        import threading
        managers = [ElasticManager(store, np=8, host=f"c{i}", ttl=30)
                    for i in range(8)]
        ts = [threading.Thread(target=m.register) for m in managers]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(managers[0].alive_nodes()) == 8
        for m in managers:
            m.deregister()

    def test_reregister_after_deregister(self, store):
        m = ElasticManager(store, np=1, host="re", ttl=0.5,
                           heartbeat_interval=0.05)
        m.register()
        m.deregister()
        m.register()            # heartbeat thread must restart
        time.sleep(0.7)         # past ttl: only heartbeats keep it alive
        assert m.alive_nodes() == ["re"]
        m.deregister()

    def test_wait_for_np(self, store):
        a = ElasticManager(store, np=2, host="wa", ttl=5,
                           heartbeat_interval=0.05)
        a.register()
        assert not a.wait_for_np(2, timeout=0.3)
        b = ElasticManager(store, np=2, host="wb", ttl=5)
        b.register()
        assert a.wait_for_np(2, timeout=5)
        a.deregister(); b.deregister()


class TestLaunchElastic:
    def test_relaunch_on_elastic_exit(self, tmp_path):
        marker = tmp_path / "count"
        code = (
            "import os,sys\n"
            f"p = {str(marker)!r}\n"
            "n = int(open(p).read()) if os.path.exists(p) else 0\n"
            "open(p,'w').write(str(n+1))\n"
            f"sys.exit({ELASTIC_EXIT_CODE} if n < 2 else 0)\n")
        rc = launch_elastic([sys.executable, "-c", code], max_restarts=5,
                            poll_interval=0.05)
        assert rc == 0
        assert int(marker.read_text()) == 3   # 1 initial + 2 relaunches

    def test_max_restarts_respected(self, tmp_path):
        code = f"import sys; sys.exit({ELASTIC_EXIT_CODE})"
        rc = launch_elastic([sys.executable, "-c", code], max_restarts=2,
                            poll_interval=0.05)
        assert rc == ELASTIC_EXIT_CODE


class TestWatchdog:
    def test_timeout_detection(self):
        mgr = CommTaskManager.instance()
        hung = []
        mgr.set_timeout_handler(lambda t: hung.append(t.name))
        mgr._scan_interval = 0.05
        mgr.start()
        tid = mgr.begin("slow_all_reduce", timeout=0.1)
        # the scan thread may be starved on a loaded host: wait for its
        # verdict, not for a duration
        deadline = time.time() + 60
        while not hung and time.time() < deadline:
            time.sleep(0.05)
        mgr.end(tid)
        mgr.stop()
        mgr.set_timeout_handler(None)
        assert "slow_all_reduce" in hung

    def test_completed_task_not_flagged(self):
        mgr = CommTaskManager.instance()
        hung = []
        mgr.set_timeout_handler(lambda t: hung.append(t.name))
        mgr._scan_interval = 0.05
        mgr.start()
        with comm_guard("fast_barrier", timeout=5):
            pass
        time.sleep(0.2)
        mgr.stop()
        mgr.set_timeout_handler(None)
        assert "fast_barrier" not in hung

    def test_enable_disable_wrapping(self):
        import paddle_tpu.distributed as dist
        import paddle_tpu.distributed.collective as coll
        orig = coll.all_reduce
        pkg_orig = dist.all_reduce
        enable_comm_watchdog(timeout=60)
        assert coll.all_reduce is not orig
        # the package re-export must be guarded too
        assert dist.all_reduce is coll.all_reduce
        disable_comm_watchdog()
        assert coll.all_reduce is orig
        assert dist.all_reduce is pkg_orig


class TestFaultTolerance:
    def test_checkpoint_roundtrip_and_prune(self, tmp_path):
        d = str(tmp_path)
        for step in range(5):
            save_checkpoint({"step": step, "w": np.ones(3) * step}, d, step,
                            keep_last_n=2)
        assert latest_checkpoint(d).endswith("step_4")
        state, step = load_checkpoint(d)
        assert step == 4 and state["step"] == 4
        import glob
        assert len(glob.glob(os.path.join(d, "step_*"))) == 2

    def test_preemption_handler(self):
        h = PreemptionHandler(signals=(signal.SIGUSR1,)).install()
        fired = []
        h.on_preemption(lambda: fired.append(1))
        assert not h.preempted()
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.1)
        assert h.preempted() and fired
        h.uninstall()

    def test_run_with_resume_full_cycle(self, tmp_path):
        """Simulated preemption mid-training in a child process, then the
        relaunch resumes from the checkpoint."""
        d = str(tmp_path / "ckpt")
        script = f"""
import sys, os, signal
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import jax; jax.config.update("jax_platforms", "cpu")
from paddle_tpu.distributed.fault_tolerance import run_with_resume, save_checkpoint

def loop(state, start_step, should_stop):
    step = start_step
    while step < 10:
        step += 1
        save_checkpoint({{"step": step}}, {d!r}, step)
        if step == 4 and start_step == 0:
            os.kill(os.getpid(), signal.SIGTERM)   # preemption notice
        if should_stop():
            return "preempted"
    return "done"

r = run_with_resume(loop, {d!r})
print("RESULT:", r)
"""
        p1 = subprocess.run([sys.executable, "-c", script],
                            capture_output=True, text=True, timeout=120)
        assert p1.returncode == ELASTIC_EXIT_CODE, p1.stderr
        # relaunch (what launch_elastic would do)
        p2 = subprocess.run([sys.executable, "-c", script],
                            capture_output=True, text=True, timeout=120)
        assert p2.returncode == 0, p2.stderr
        assert "RESULT: done" in p2.stdout
        _, step = load_checkpoint(d)
        assert step == 10


# Trainer for the coordinated-restart test: resumes the step counter from
# its checkpoint file, trains to TOTAL steps, and on generation 0 rank 1
# dies mid-training (simulated hardware fault).  Rank 0's generation-0
# run must NOT finish by step count: on a fast machine it could complete
# all TOTAL steps before its controller observes rank 1's death, leaving
# generation 1 nothing to do (rank0.json would finish with gen=0 and the
# resume assertions flake).  So rank 0 stalls one step short of the end
# and waits for the controller's coordinated teardown (SIGTERM) — the
# gen-0 run is ended by the CONTROLLER's restart observation, never by
# the trainer racing it, and generation >= 1 always resumes with real
# work left (the deterministic fix for the pre-existing timing flake).
_COORD_TRAINER = r"""
import json, os, sys, time
ckpt_dir, total = sys.argv[1], int(sys.argv[2])
rank = int(os.environ["PADDLE_TRAINER_ID"])
gen = int(os.environ["PADDLE_ELASTIC_GEN"])
path = os.path.join(ckpt_dir, f"rank{rank}.json")
start = 0
if os.path.exists(path):
    start = json.load(open(path))["step"] + 1
log = open(os.path.join(ckpt_dir, f"trace_rank{rank}.log"), "a")
for step in range(start, total):
    time.sleep(0.05)                       # "training"
    tmp = path + ".tmp"
    json.dump({"step": step, "gen": gen}, open(tmp, "w"))
    os.replace(tmp, path)                  # atomic: SIGTERM-safe resume
    print(f"gen={gen} step={step}", file=log, flush=True)
    if rank == 1 and gen == 0 and step == 2:
        os._exit(17)                       # mid-training fault
    if rank == 0 and gen == 0 and step == total - 2:
        # survive until the controller's coordinated teardown — but
        # BOUNDED: if the controller never observes rank 1's death
        # (the regression this test exists to catch), fail fast with
        # a diagnostic instead of hanging the suite
        deadline = time.time() + 60.0
        while time.time() < deadline:
            time.sleep(0.05)
        print("gen-0 rank 0 never torn down by the controller",
              file=sys.stderr)
        sys.exit(3)
"""


class TestCoordinatedElasticRestart:
    def test_two_node_coordinated_restart_and_resume(self, store, tmp_path):
        """VERDICT r3 item 9: kill one rank mid-training; ALL nodes tear
        down, re-rendezvous via the shared restart generation, relaunch,
        and training resumes from checkpoints to completion."""
        import threading

        total = 6
        trainer = str(tmp_path / "trainer.py")
        with open(trainer, "w") as f:
            f.write(_COORD_TRAINER)

        def factory(rank, nnodes, gen):
            return [sys.executable, trainer, str(tmp_path), str(total)]

        controllers = [
            # ttl generous vs. the 0.05s poll: on a loaded CI host the
            # heartbeat thread can be starved for seconds, and a slipped
            # heartbeat shows up as a spurious membership restart; 5s ttl
            # made this test flake under load
            ElasticController(store, node_id=f"node-{i}", nnodes=2,
                              cmd_factory=factory, max_restarts=8,
                              poll_interval=0.05, rendezvous_timeout=120,
                              ttl=30.0)
            for i in range(2)
        ]
        codes = {}

        def run(i):
            codes[i] = controllers[i].run()

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads), "controllers hung"
        assert codes == {0: 0, 1: 0}, codes

        # both ranks completed every step after the resume
        import json
        for rank in range(2):
            state = json.load(open(tmp_path / f"rank{rank}.json"))
            assert state["step"] == total - 1, state
            assert state["gen"] >= 1          # finished in a later generation

        # BOTH controllers observed the coordinated restart (not just the
        # failing node), and the surviving rank 0 re-ran under gen >= 1
        for c in controllers:
            assert len(c.generations_seen) >= 2, c.generations_seen
        trace0 = (tmp_path / "trace_rank0.log").read_text()
        assert "gen=1" in trace0 or "gen=2" in trace0, trace0

        # resume actually skipped completed work: rank 0's second run
        # starts past step 0
        lines = [l for l in trace0.splitlines() if not l.startswith("gen=0")]
        assert lines and not lines[0].endswith("step=0"), trace0

    def test_degraded_world_when_peer_controller_dies(self, store,
                                                      tmp_path):
        """A whole peer CONTROLLER vanishing (not just its trainer) must
        not hang the survivor: heartbeat expiry bumps the generation and
        the survivor re-rendezvouses at min_nodes with a REDUCED world."""
        import threading

        trainer = str(tmp_path / "trainer.py")
        with open(trainer, "w") as f:
            f.write(
                "import json, os, sys, time\n"
                "time.sleep(0.3)\n"
                "json.dump({'world': os.environ['PADDLE_TRAINERS_NUM'],"
                " 'gen': os.environ['PADDLE_ELASTIC_GEN']},"
                " open(sys.argv[1] + '/run_' +"
                " os.environ['PADDLE_ELASTIC_GEN'] + '_' +"
                " os.environ['PADDLE_TRAINER_ID'] + '.json', 'w'))\n")

        def factory(rank, nnodes, gen):
            return [sys.executable, trainer, str(tmp_path)]

        survivor = ElasticController(
            store, node_id="sv", nnodes=2, cmd_factory=factory,
            min_nodes=1, max_restarts=3, poll_interval=0.05,
            rendezvous_timeout=4, ttl=0.6)
        # the doomed peer: registers (so gen-0 rendezvous completes at
        # full size) then its controller "crashes" — heartbeat stops
        doomed = ElasticManager(store, np=2, host="dd", ttl=0.6,
                                heartbeat_interval=0.1)
        doomed.register()
        store.add("elastic/gen/0/ready", 1)   # doomed posts ready, then dies

        def kill_later():
            time.sleep(0.6)
            doomed._stop.set()                # heartbeat thread halts

        threading.Thread(target=kill_later).start()
        code = survivor.run()
        assert code == 0, code
        import json, glob
        runs = sorted(glob.glob(str(tmp_path / "run_*.json")))
        final = json.load(open(runs[-1]))
        assert final["world"] == "1", (runs, final)   # degraded world
        assert int(final["gen"]) >= 1
        assert len(survivor.generations_seen) >= 2
