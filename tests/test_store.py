"""TCPStore tests: native server/client, multi-process rendezvous, barrier —
mirrors the reference's single-host multi-process collective test strategy
(SURVEY §4.4)."""
import multiprocessing as mp
import pickle
import threading
import time

import pytest

from paddle_tpu.distributed.store import (
    TCPStore, _PyClient, _PyStoreServer, barrier,
)


@pytest.fixture()
def master():
    s = TCPStore("127.0.0.1", 0, is_master=True, world_size=1, timeout=10)
    yield s
    s.close()


class TestNativeStore:
    def test_native_backend_active(self, master):
        from paddle_tpu.distributed.store import _NativeClient
        assert isinstance(master._client, _NativeClient)

    def test_set_get(self, master):
        master.set("k1", b"hello")
        assert master.get("k1") == b"hello"
        master.set("k1", "text-value")
        assert master.get("k1") == b"text-value"

    def test_get_blocks_until_set(self, master):
        worker = TCPStore("127.0.0.1", master.port, is_master=False,
                          timeout=10)

        def setter():
            time.sleep(0.2)
            worker.set("late_key", b"v")

        t = threading.Thread(target=setter)
        t.start()
        t0 = time.time()
        assert master.get("late_key", timeout=5) == b"v"
        assert time.time() - t0 >= 0.15
        t.join()

    def test_get_timeout(self, master):
        with pytest.raises(TimeoutError):
            master.get("never_set", timeout=0.2)

    def test_add_counter(self, master):
        assert master.add("cnt", 1) == 1
        assert master.add("cnt", 2) == 3
        assert master.add("cnt", -1) == 2

    def test_wait_and_check(self, master):
        assert not master.check("w1")
        master.set("w1", b"x")
        master.wait("w1", timeout=1)
        assert master.check("w1")

    def test_large_value(self, master):
        blob = bytes(range(256)) * 4096   # 1 MiB
        master.set("big", blob)
        assert master.get("big") == blob

    def test_multiple_clients(self, master):
        clients = [TCPStore("127.0.0.1", master.port, is_master=False,
                            timeout=10) for _ in range(4)]
        for i, c in enumerate(clients):
            c.set(f"client_{i}", str(i))
        for i, c in enumerate(clients):
            assert master.get(f"client_{i}") == str(i).encode()


class TestBarrierReuse:
    def test_same_key_multiple_generations(self):
        master = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        try:
            # world_size=1: each call is its own generation and must
            # complete rather than sail through on a stale release key
            for _ in range(3):
                barrier(master, "epoch", 1, timeout=2)
            assert master.add("barrier/epoch", 0) == 3
        finally:
            master.close()

    def test_server_stops_with_live_clients(self):
        master = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        extra = TCPStore("127.0.0.1", master.port, is_master=False,
                         timeout=10)
        # must not hang on extra's open connection (a hang fails at
        # the suite's limit a test: tests/conftest.py)
        master.close()
        extra.close()

    def test_check_on_a_dead_store_raises(self):
        # "absent" and "the store is gone" must differ: p2p.recv polls
        # check(), and a dead store that read as "not yet" hung the
        # peers of a rank 0 that had exited (tests/test_p2p.py)
        master = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        extra = TCPStore("127.0.0.1", master.port, is_master=False,
                         timeout=10)
        assert extra.check("never-set") is False
        master.close()
        with pytest.raises(ConnectionError):
            extra.check("never-set")
        extra.close()


def _rank_proc(rank, world, port, results):
    from paddle_tpu.framework.backend_guard import helper_process_init
    helper_process_init()   # spawned children never claim the chip
    store = TCPStore("127.0.0.1", port, is_master=False, world_size=world,
                     timeout=20)
    store.set(f"rank/{rank}", pickle.dumps({"rank": rank}))
    barrier(store, "join", world)
    # after barrier every rank sees every other rank's entry immediately
    got = sorted(pickle.loads(store.get(f"rank/{r}"))["rank"]
                 for r in range(world))
    results.put((rank, got))


class TestMultiProcess:
    def test_rendezvous_and_barrier(self):
        world = 3
        master = TCPStore("127.0.0.1", 0, is_master=True, world_size=world,
                          timeout=20)
        ctx = mp.get_context("spawn")
        results = ctx.Queue()
        procs = [ctx.Process(target=_rank_proc,
                             args=(r, world, master.port, results))
                 for r in range(world)]
        for p in procs:
            p.start()
        seen = {}
        for _ in range(world):
            rank, got = results.get(timeout=60)
            seen[rank] = got
        for p in procs:
            p.join(timeout=30)
        assert set(seen) == {0, 1, 2}
        for got in seen.values():
            assert got == [0, 1, 2]


class TestPyFallback:
    def test_python_server_and_client_protocol(self):
        srv = _PyStoreServer(0)
        try:
            c = _PyClient("127.0.0.1", srv.port, timeout=10)
            assert c.set(b"k", b"v")
            assert c.get(b"k", 1000) == b"v"
            assert c.add(b"n", 5) == 5
            assert c.add(b"n", 5) == 10
            assert c.wait(b"k", 1000)
            assert c.check(b"k")
            assert not c.check(b"missing")
            assert c.get(b"missing", 100) is None
            c.close()
        finally:
            srv.stop()

    def test_native_client_python_server_interop(self):
        # wire protocol is shared: native client against python server
        from paddle_tpu.distributed.store import _NativeClient, _load_lib
        srv = _PyStoreServer(0)
        try:
            lib = _load_lib()
            c = _NativeClient(lib, "127.0.0.1", srv.port, timeout=10)
            assert c.set(b"ik", b"iv")
            assert c.get(b"ik", 1000) == b"iv"
            assert c.add(b"ic", 7) == 7
            c.close()
        finally:
            srv.stop()
