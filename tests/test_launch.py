"""Launch controller: multi-process supervision, env contract, per-rank
logs, failure teardown, elastic restart (reference:
launch/controllers/collective.py, job/container.py, elastic manager)."""
import os
import sys
import textwrap

import pytest

from paddle_tpu.distributed.launch.controller import LocalController


def _script(tmp_path, body):
    p = tmp_path / "worker.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestLocalController:
    def test_env_contract_and_logs(self, tmp_path):
        script = _script(tmp_path, """
            import os
            rank = os.environ["PADDLE_TRAINER_ID"]
            world = os.environ["PADDLE_TRAINERS_NUM"]
            assert os.environ["PADDLE_MASTER"]
            eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
            assert len(eps) == int(world)
            print(f"rank {rank} of {world} ok", flush=True)
        """)
        log_dir = str(tmp_path / "logs")
        code = LocalController(script, nproc=3, log_dir=log_dir,
                               watch_rank0=False).run()
        assert code == 0
        for r in range(3):
            text = open(os.path.join(log_dir, f"workerlog.{r}")).read()
            assert f"rank {r} of 3 ok" in text

    def test_failure_tears_down_peers(self, tmp_path):
        outlived = tmp_path / "a-peer-ran-to-completion"
        script = _script(tmp_path, f"""
            import os, sys, time
            if os.environ["PADDLE_TRAINER_ID"] == "1":
                sys.exit(7)
            time.sleep(60)   # peers must not run to completion
            open({str(outlived)!r}, "w").close()
        """)
        code = LocalController(script, nproc=3, watch_rank0=False).run()
        assert code == 7
        assert not outlived.exists()       # torn down, not waited out

    def test_elastic_restart_then_success(self, tmp_path):
        marker = tmp_path / "attempt"
        script = _script(tmp_path, f"""
            import os, sys
            marker = {str(marker)!r} + os.environ["PADDLE_TRAINER_ID"]
            if not os.path.exists(marker):
                open(marker, "w").close()
                if os.environ["PADDLE_TRAINER_ID"] == "0":
                    sys.exit(101)     # fail the first attempt
        """)
        code = LocalController(script, nproc=2, elastic_level=1,
                               max_restarts=2, watch_rank0=False).run()
        assert code == 0               # second attempt succeeds

    def test_helper_ranks_marked_cpu_only(self, tmp_path):
        script = _script(tmp_path, """
            import os, sys
            rank = os.environ["PADDLE_TRAINER_ID"]
            has = os.environ.get("PADDLE_TPU_HELPER_CPU")
            if rank == "0":
                assert has is None
            else:
                assert has == "1"
        """)
        assert LocalController(script, nproc=2, watch_rank0=False).run() == 0

    @pytest.mark.parametrize("has_tpu,helper_cpu_only,nproc,refused", [
        (True, False, 2, True),     # every rank would take every chip
        (True, True, 2, False),     # only rank 0 reaches the chips
        (True, False, 1, False),    # one process drives the host's chips
        (False, False, 2, False),   # no chip to fight over
    ])
    def test_several_chip_ranks_on_one_tpu_host_are_refused(
            self, tmp_path, monkeypatch, has_tpu, helper_cpu_only, nproc,
            refused):
        from paddle_tpu.distributed.launch import controller
        monkeypatch.setattr(controller, "_host_has_tpu", lambda: has_tpu)
        script = _script(tmp_path, "pass")
        kw = dict(nproc=nproc, helper_cpu_only=helper_cpu_only)
        if refused:
            with pytest.raises(RuntimeError, match="one process"):
                LocalController(script, **kw)
        else:
            LocalController(script, **kw)

    def test_launch_main_multiproc(self, tmp_path):
        from paddle_tpu.distributed.launch.main import main
        script = _script(tmp_path, """
            import os
            print("hello from", os.environ["PADDLE_TRAINER_ID"])
        """)
        with pytest.raises(SystemExit) as e:
            main(["--nproc_per_node", "2", "--log_dir",
                  str(tmp_path / "l"), script])
        assert e.value.code == 0

    def test_multinode_endpoint_exchange(self, tmp_path):
        """Two launchers (nnodes=2) on one machine: the node-0 launcher
        hosts the master store, both exchange endpoint lists, and every
        child sees the full world-sized global contract in node order."""
        import socket
        import threading

        script = _script(tmp_path, """
            import os
            eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            assert len(eps) == world == 4, (eps, world)
            assert len(set(eps)) == 4          # all distinct
            assert os.environ["PADDLE_MASTER_BOUND"] == "1"
            print(f"rank {rank} sees {len(eps)} endpoints", flush=True)
        """)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        master = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        codes = {}

        def launch(node_rank):
            codes[node_rank] = LocalController(
                script, nproc=2, nnodes=2, node_rank=node_rank,
                master=master, watch_rank0=False).run()

        t1 = threading.Thread(target=launch, args=(1,))
        t1.start()
        launch(0)
        t1.join(timeout=60)
        assert codes == {0: 0, 1: 0}

    def test_popen_failure_closes_log_fd(self, tmp_path):
        from paddle_tpu.distributed.launch.controller import ProcContext
        pc = ProcContext(0, ["/nonexistent-binary-xyz"], dict(os.environ),
                         str(tmp_path / "log.0"))
        with pytest.raises(OSError):
            pc.start()
        assert pc._log_f is None       # fd released on Popen failure
