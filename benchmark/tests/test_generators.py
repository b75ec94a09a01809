"""Both generators: byte-identical for one seed, different for two, and
the same set of sizes whatever the seed."""
import json
from pathlib import Path

import numpy as np

from generators import closed_loop, packed_docs

BENCH = Path(__file__).resolve().parent.parent


def traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def requests(seed, n):
    g = closed_loop.build(traffic("closed8-chat"), 32768, seed)
    return [g.next_request() for _ in range(n)]


def test_closed_loop_is_a_function_of_the_seed():
    a, b, c = requests(2**31 + 7, 200), requests(2**31 + 7, 200), requests(8, 200)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, c))


def test_every_seed_sends_the_same_sizes_in_the_same_order():
    t = traffic("closed8-chat")
    n = t["levels"]
    a, c = requests(1, n * n), requests(2, n * n)
    sizes = lambda rs: [(len(p), o) for p, o in rs]  # noqa: E731
    assert sizes(a) == sizes(c) and len(set(sizes(a))) == n * n
    assert not np.array_equal(a[0][0], c[0][0])             # other ids
    lens = sorted({len(p) for p, _ in a})
    assert len(lens) == n and lens[0] >= 32 and lens[-1] <= 2048
    assert 300 < float(np.median([len(p) for p, _ in a])) < 480
    outs = sorted({o for _, o in a})
    assert len(outs) == n and outs[0] >= 8 and outs[-1] <= 256
    assert all(p.dtype == np.int32 and p.max() < 32768 for p, _ in a)


def test_every_block_carries_the_same_tokens():
    n = traffic("closed8-chat")["levels"]
    for seed in (3, 4):
        rs = requests(seed, 3 * n * n)
        blocks = [rs[i:i + n] for i in range(0, len(rs), n)]
        assert len({sum(len(p) for p, _ in b) for b in blocks}) == 1
        assert len({sum(o for _, o in b) for b in blocks}) == 1
        assert all(len({len(p) for p, _ in b}) == n for b in blocks)


def batches(seed, n):
    g = packed_docs.build(traffic("pretrain-4k"), 32768, seed)
    return [g.next_batch() for _ in range(n)]


def test_packed_docs_is_a_function_of_the_seed():
    a, b, c = batches(5, 4), batches(5, 4), batches(6, 4)
    for (x1, y1), (x2, y2) in zip(a, b):
        assert x1.tobytes() == x2.tobytes() and y1.tobytes() == y2.tobytes()
    assert a[0][0].tobytes() != c[0][0].tobytes()
    x, y = a[0]
    assert x.shape == y.shape == (1, 4096) and x.dtype == np.int32
    assert np.array_equal(x[0, 1:], y[0, :-1])          # one shift apart
    assert not np.array_equal(a[0][0], a[1][0])         # rows all differ
    # Zipf: a few ids carry much of the mass
    _, counts = np.unique(np.concatenate([b_[0].ravel() for b_ in a]),
                          return_counts=True)
    assert np.sort(counts)[-10:].sum() > 0.2 * counts.sum()


def test_the_fetch_lag_is_the_traffic_files_and_0_if_left_out():
    t = traffic("pretrain-4k")
    g = packed_docs.build(t, 32768, 1)
    assert (g.fetch_every, g.fetch_lag) == (t["fetch_every"], t["fetch_lag"])
    assert 0 < g.fetch_lag < g.fetch_every
    t.pop("fetch_lag")
    assert packed_docs.build(t, 32768, 1).fetch_lag == 0
