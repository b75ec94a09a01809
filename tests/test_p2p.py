"""Eager p2p + object collectives: multi-process localhost clusters over the
TCPStore substrate (reference: communication/batch_isend_irecv.py,
test/collective p2p tests)."""
import multiprocessing as mp
import os
import queue
import time

import numpy as np
import pytest


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env(rank, world, port):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(world)
    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"


_RANKS_TIMEOUT_S = 90.0   # a healthy run: 3 spawns importing jax, 10-30 s


def _rank_main(body, rank, world, port, q, release):
    """One rank of a localhost cluster: run ``body(rank, world)``, report
    to the parent, then stay alive until the parent has heard from EVERY
    rank.  Rank 0 hosts the store in its own process: a rank 0 that
    returned as soon as it was done took the store with it, and with it
    the payloads it had just posted for its peers (the last collective's
    results) — on a loaded host the peers, a poll behind, then waited on
    a dead store for good."""
    try:
        from paddle_tpu.framework.backend_guard import helper_process_init
        helper_process_init()
        _env(rank, world, port)
        status = body(rank, world) or "ok"
    except Exception as e:   # noqa: BLE001
        import traceback
        status = f"FAIL: {e}\n{traceback.format_exc()}"
    q.put((rank, status))
    release.wait(_RANKS_TIMEOUT_S)


def _run_ranks(body, world=3):
    """Spawn ``world`` ranks of ``body`` and collect one status each;
    says WHICH rank is missing when one does not report in time."""
    port = _free_port()
    ctx = mp.get_context("spawn")
    q, release = ctx.Queue(), ctx.Event()
    procs = [ctx.Process(target=_rank_main,
                         args=(body, r, world, port, q, release))
             for r in range(world)]
    for p in procs:
        p.start()
    results = {}
    deadline = time.monotonic() + _RANKS_TIMEOUT_S
    try:
        while len(results) < world:
            try:
                rank, status = q.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                missing = sorted(set(range(world)) - set(results))
                raise AssertionError(
                    f"ranks {missing} did not report within "
                    f"{_RANKS_TIMEOUT_S:.0f} s (alive: "
                    f"{[p.is_alive() for p in procs]}; reported: "
                    f"{results})") from None
            results[rank] = status
    finally:
        release.set()
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
    assert all(v == "ok" for v in results.values()), results


def _p2p_proc(rank, world):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import P2POp, batch_isend_irecv
    from paddle_tpu.distributed import p2p

    # --- blocking ring exchange: rank r sends r*ones to (r+1) % world
    nxt, prv = (rank + 1) % world, (rank - 1) % world
    out = paddle.to_tensor(np.full((4,), rank, np.float32))
    got = paddle.to_tensor(np.zeros((4,), np.float32))
    if rank % 2 == 0:
        dist.send(out, dst=nxt)
        dist.recv(got, src=prv)
    else:
        dist.recv(got, src=prv)
        dist.send(out, dst=nxt)
    np.testing.assert_allclose(got.numpy(), np.full((4,), prv))

    # --- isend/irecv round trip with explicit wait
    t_in = paddle.to_tensor(np.arange(6, dtype=np.float32) + 100 * rank)
    t_out = paddle.to_tensor(np.zeros(6, np.float32))
    tasks = [p2p.isend(t_in, dst=nxt, tag="async"),
             p2p.irecv(t_out, src=prv, tag="async", timeout=60)]
    for t in tasks:
        t.wait(timeout=60)
    np.testing.assert_allclose(
        t_out.numpy(), np.arange(6, dtype=np.float32) + 100 * prv)

    # --- batch_isend_irecv symmetric exchange
    b_in = paddle.to_tensor(np.full((2, 2), rank, np.float32))
    b_out = paddle.to_tensor(np.zeros((2, 2), np.float32))
    ops = [P2POp(p2p.isend, b_in, nxt, tag="batch"),
           P2POp(p2p.irecv, b_out, prv, tag="batch")]
    for t in batch_isend_irecv(ops):
        t.wait(timeout=60)
    np.testing.assert_allclose(b_out.numpy(), np.full((2, 2), prv))

    # --- object collectives
    objs = []
    dist.all_gather_object(objs, {"rank": rank})
    assert [o["rank"] for o in objs] == list(range(world))

    blist = [f"payload-{rank}", rank] if rank == 0 else [None, None]
    dist.broadcast_object_list(blist, src=0)
    assert blist == ["payload-0", 0]

    scattered = []
    dist.scatter_object_list(
        scattered, [f"for-{r}" for r in range(world)], src=0)
    assert scattered == [f"for-{rank}"]

    # --- list-form all_to_all: rank i's slot j lands on rank j slot i
    ins = [paddle.to_tensor(np.array([rank * 10 + j], np.float32))
           for j in range(world)]
    outs = []
    dist.all_to_all(outs, ins)
    np.testing.assert_allclose(
        np.concatenate([o.numpy() for o in outs]),
        np.array([r * 10 + rank for r in range(world)], np.float32))



class TestP2PMultiProcess:
    def test_ring_exchange_three_ranks(self):
        _run_ranks(_p2p_proc)


class TestP2PSingleProcess:
    def test_send_recv_self_roundtrip(self):
        # world=1: send-to-self then recv-from-self through the store
        import paddle_tpu as paddle
        from paddle_tpu.distributed import p2p
        from paddle_tpu.distributed.store import TCPStore
        p2p._reset_state()
        st = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        p2p._state.store = st
        try:
            x = paddle.to_tensor(np.arange(4, dtype=np.float32))
            y = paddle.to_tensor(np.zeros(4, np.float32))
            p2p.send(x, dst=0)
            p2p.recv(y, src=0, timeout=5)
            np.testing.assert_allclose(y.numpy(), x.numpy())
        finally:
            st.close()
            p2p._reset_state()

    def test_isend_sequence_reserved_at_issue_time(self):
        # two isends to the same peer must deliver in issue order even if
        # their worker threads are scheduled out of order
        import paddle_tpu as paddle
        from paddle_tpu.distributed import p2p
        from paddle_tpu.distributed.store import TCPStore
        p2p._reset_state()
        st = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        p2p._state.store = st
        try:
            a = paddle.to_tensor(np.array([1.0], np.float32))
            b = paddle.to_tensor(np.array([2.0], np.float32))
            t1 = p2p.isend(a, dst=0)
            t2 = p2p.isend(b, dst=0)
            t1.wait(30); t2.wait(30)
            r1 = paddle.to_tensor(np.zeros(1, np.float32))
            r2 = paddle.to_tensor(np.zeros(1, np.float32))
            p2p.recv(r1, src=0, timeout=10)
            p2p.recv(r2, src=0, timeout=10)
            assert float(r1.numpy()[0]) == 1.0
            assert float(r2.numpy()[0]) == 2.0
        finally:
            st.close()
            p2p._reset_state()

    def test_batch_isend_irecv_preserves_input_order(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed import P2POp, batch_isend_irecv
        from paddle_tpu.distributed import p2p
        from paddle_tpu.distributed.store import TCPStore
        p2p._reset_state()
        st = TCPStore("127.0.0.1", 0, is_master=True, timeout=10)
        p2p._state.store = st
        try:
            t_in = paddle.to_tensor(np.array([5.0], np.float32))
            t_out = paddle.to_tensor(np.zeros(1, np.float32))
            # recv listed FIRST: tasks[0] must still be the recv task
            ops = [P2POp(p2p.irecv, t_out, 0), P2POp(p2p.isend, t_in, 0)]
            tasks = batch_isend_irecv(ops)
            tasks[0].wait(30)   # reference contract: tasks[i] <-> ops[i]
            np.testing.assert_allclose(t_out.numpy(), [5.0])
            tasks[1].wait(30)
        finally:
            st.close()
            p2p._reset_state()

    def test_p2pop_validates_op(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed import P2POp
        with pytest.raises(ValueError):
            P2POp(print, paddle.to_tensor(np.zeros(1)), 0)

    def test_object_collectives_world1(self):
        import paddle_tpu.distributed as dist
        objs = []
        dist.all_gather_object(objs, 7)
        assert objs == [7]
        lst = ["a"]
        dist.broadcast_object_list(lst, src=0)
        assert lst == ["a"]
        out = []
        dist.scatter_object_list(out, ["x", "y"], src=0)
        assert out == ["x"]


def _mp_collective_proc(rank, world):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    # all_reduce sum: every rank ends with 0+1+2
    x = paddle.to_tensor(np.full((3,), float(rank), np.float32))
    dist.all_reduce(x)
    np.testing.assert_allclose(x.numpy(), sum(range(world)))

    # all_reduce max
    m = paddle.to_tensor(np.array([float(rank)], np.float32))
    dist.all_reduce(m, op=dist.ReduceOp.MAX)
    assert float(m.numpy()[0]) == world - 1

    # broadcast from rank 1
    b = paddle.to_tensor(np.full((2,), float(rank), np.float32))
    dist.broadcast(b, src=1)
    np.testing.assert_allclose(b.numpy(), 1.0)

    # all_gather: rank-major pieces
    parts = []
    dist.all_gather(parts, paddle.to_tensor(
        np.array([rank * 10.0], np.float32)))
    assert [float(p.numpy()[0]) for p in parts] == \
        [r * 10.0 for r in range(world)]

    # reduce to dst=2
    r = paddle.to_tensor(np.array([1.0], np.float32))
    dist.reduce(r, dst=world - 1)
    if rank == world - 1:
        assert float(r.numpy()[0]) == world

    # scatter from rank 0
    s = paddle.to_tensor(np.zeros((2,), np.float32))
    chunks = [paddle.to_tensor(np.full((2,), 7.0 + i, np.float32))
              for i in range(world)] if rank == 0 else None
    dist.scatter(s, chunks, src=0)
    np.testing.assert_allclose(s.numpy(), 7.0 + rank)

    # reduce_scatter: world*L input, each keeps its reduced slice
    inp = paddle.to_tensor(
        np.arange(world * 2, dtype=np.float32) + rank)
    out = paddle.to_tensor(np.zeros(2, np.float32))
    dist.reduce_scatter(out, inp)
    base = np.arange(world * 2, dtype=np.float32) * world + \
        sum(range(world))
    np.testing.assert_allclose(out.numpy(),
                               base[rank * 2:(rank + 1) * 2])


class TestMultiProcessEagerCollectives:
    def test_three_rank_collectives(self):
        _run_ranks(_mp_collective_proc)


def _subgroup_proc(rank, world):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    # subgroup {0, 2}: rank 1 must be a no-op non-member
    g = dist.new_group(ranks=[0, 2])
    x = paddle.to_tensor(np.array([float(rank + 1)], np.float32))
    dist.all_reduce(x, group=g)
    if rank in (0, 2):
        assert float(x.numpy()[0]) == 4.0      # 1 + 3
    else:
        assert float(x.numpy()[0]) == 2.0      # untouched

    # gather / all_to_all / alltoall_single also honor the subgroup:
    # rank 1 returns immediately instead of blocking in recv
    gl = []
    res = dist.gather(x, gather_list=gl, dst=0, group=g)
    if rank == 0:
        got = sorted(float(t.numpy()[0]) for t in gl)
        assert got == [4.0, 4.0], got        # both members post-allreduce
    elif rank == 1:
        assert res is None

    ins = [paddle.to_tensor(np.array([rank * 10 + j], np.float32))
           for j in range(2)]
    outs = []
    res = dist.all_to_all(outs, ins, group=g)
    if rank in (0, 2):
        me = [0, 2].index(rank)
        vals = [float(t.numpy()[0]) for t in outs]
        assert vals == [0 * 10 + me, 2 * 10 + me], vals
    else:
        assert res == [] and outs == []

    single_in = paddle.to_tensor(
        np.array([rank * 10, rank * 10 + 1], np.float32))
    res = dist.alltoall_single(None, single_in, group=g)
    if rank in (0, 2):
        me = [0, 2].index(rank)
        np.testing.assert_allclose(
            res.numpy(), [0 * 10 + me, 2 * 10 + me])

    # cross-process barrier actually synchronizes (the ranks leave a
    # first barrier together: rank 1 takes no part in the subgroup's
    # exchanges above and may stand seconds apart from the other two)
    import time
    dist.barrier()
    t0 = time.monotonic()
    if rank == 0:
        time.sleep(1.0)
    dist.barrier()
    elapsed = time.monotonic() - t0
    assert elapsed >= 0.9, elapsed              # everyone waited on 0

    # reduce_scatter rejects non-divisible dim 0
    bad_out = paddle.to_tensor(np.zeros(2, np.float32))
    bad_in = paddle.to_tensor(np.zeros(7, np.float32))
    try:
        dist.reduce_scatter(bad_out, bad_in)
        return "no-error"
    except ValueError:
        pass
    # input of reduce_scatter must NOT be mutated
    keep = paddle.to_tensor(
        np.arange(world * 2, dtype=np.float32) + rank)
    before = keep.numpy().copy()
    out = paddle.to_tensor(np.zeros(2, np.float32))
    dist.reduce_scatter(out, keep)
    np.testing.assert_allclose(keep.numpy(), before)


class TestSubgroupAndBarrier:
    def test_subgroup_barrier_reduce_scatter(self):
        _run_ranks(_subgroup_proc)


def _default_group_proc(rank, world):
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    # a default-constructed group must span the launcher world, not
    # the local jax.process_count() == 1
    g = dist.new_group()
    x = paddle.to_tensor(np.array([1.0], np.float32))
    dist.all_reduce(x, group=g)
    assert float(x.numpy()[0]) == world

    # non-member src must raise, not hang
    sub = dist.new_group(ranks=[0, 2])
    if rank in (0, 2):
        try:
            dist.broadcast(paddle.to_tensor(
                np.zeros(1, np.float32)), src=1, group=sub)
            return "no-error"
        except ValueError:
            pass


class TestDefaultGroupSemantics:
    def test_default_group_spans_launcher_world(self):
        _run_ranks(_default_group_proc)
