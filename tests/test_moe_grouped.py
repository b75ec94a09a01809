"""The no-drop grouped expert product (ISSUE 33): the (token, chosen
expert) pairs grouped by expert in blocks of 16 rows
(``ops/pallas/moe_grouped_ffn.py``) against every token through every
expert (``_held_experts(first=0, count=E)``), under even routing, under
collapse (every token the same experts) and with pad positions; the
Pallas kernel interpreted against ``lax.ragged_dot`` over the same
layout; the plan's bounds; and the shares ``(first, count)`` of one layer
adding up to the whole, the shared expert counted once.

Float32 on the CPU: the two products differ in the order of float32
sums only, so 1e-5 relative is a hundred times what was read."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import (
    MoELayer, SigmoidTopKGate, SwiGLUExperts)
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
    ROUTING_FIELDS, _grouped_experts, _held_experts)
from paddle_tpu.ops.pallas import moe_grouped_ffn as G

M, H, E, K = 64, 32, 8, 2


def weights(rng, e=E):
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    return f(e, M, H), f(e, M, H), f(e, H, M)


def routing(rng, t, kind):
    if kind == "even":
        idx = np.stack([rng.permutation(E)[:K] for _ in range(t)])
    elif kind == "collapse":
        idx = np.tile(np.asarray([[5, 2]]), (t, 1))
    else:                                   # skewed: most pairs on expert 0
        idx = np.stack([[0, 1 + rng.integers(E - 1)] for _ in range(t)])
    w = rng.uniform(0.1, 1.0, (t, K))
    return jnp.asarray(idx, jnp.int32), jnp.asarray(w, jnp.float32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


class TestGroupedProduct:
    @pytest.mark.parametrize("interpret", [False, True])
    @pytest.mark.parametrize("kind", ["even", "collapse", "skewed"])
    @pytest.mark.parametrize("t", [8, 37])
    def test_matches_every_token_through_every_expert(self, t, kind,
                                                      interpret):
        rng = np.random.default_rng(33)
        x = jnp.asarray(rng.standard_normal((t, M)), jnp.float32)
        wg, wu, wd = weights(rng)
        idx, w = routing(rng, t, kind)
        want, _ = _held_experts.raw_fn(x, idx, w, 0, wg, wu, wd)
        got, counts, rows = G.grouped_swiglu(
            x, idx, w, jnp.ones((t, K), bool), wg, wu, wd,
            interpret=interpret)
        assert rel(got, want) < 1e-5
        per = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
        np.testing.assert_array_equal(counts, per)  # nothing dropped
        assert rows == (-(-per // G.BLOCK_ROWS) * G.BLOCK_ROWS).sum()
        assert rows <= t * K + (per > 0).sum() * (G.BLOCK_ROWS - 1)

    @pytest.mark.parametrize("interpret", [False, True])
    def test_pad_positions_touch_no_expert(self, interpret):
        """Pads carry garbage and an expert of their own choosing: they
        get no row, their output is zero, the others' is unchanged."""
        rng = np.random.default_rng(34)
        t, real = 24, 9
        x = jnp.asarray(rng.standard_normal((t, M)), jnp.float32)
        x = x.at[real:].set(jnp.nan)
        wg, wu, wd = weights(rng)
        idx, w = routing(rng, t, "even")
        idx = idx.at[real:].set(7).at[:real].set(idx[:real] % 7)
        mask = jnp.arange(t) < real
        want, _ = _held_experts.raw_fn(x[:real], idx[:real], w[:real], 0,
                                       wg, wu, wd)
        y, st = _grouped_experts.raw_fn(
            jnp.where(mask[:, None], x, 0.0), idx, w, mask, 0, wg, wu, wd)
        assert rel(y[:real], want) < 1e-5
        assert not np.asarray(y[real:]).any()
        got = dict(zip(ROUTING_FIELDS, (int(v) for v in st)))
        assert got["slots"] == got["held"] == real * K
        per = np.bincount(np.asarray(idx[:real]).reshape(-1), minlength=E)
        assert per[7] == 0 and got["touched"] == (per > 0).sum()
        assert got["most"] == per.max()
        # and through the kernel, NaN pads and all: the 0/1 dispatch
        # would carry a NaN (0 x NaN), so a caller zeroes its pads; the
        # product itself gives them no row
        got, *_ = G.grouped_swiglu(
            jnp.where(mask[:, None], x, 0.0), idx, w,
            jnp.broadcast_to(mask[:, None], idx.shape), wg, wu, wd,
            interpret=interpret)
        assert rel(got[:real], want) < 1e-5

    @pytest.mark.parametrize("case", ["even", "nobody_chose_3", "one_row"])
    @pytest.mark.parametrize("tiles", [1, 2, 4])
    def test_tiled_kernel_against_its_oracle(self, monkeypatch, tiles, case):
        """An expert wider than a VMEM tile (ISSUE 44: 2,048 x 2,048 is
        four tiles of 512; here the tile is 32 and the width 32, 64 and
        128): the kernel, interpreted, a tile of the width at a time with
        its float32 partial sums, against ``_ffn_xla`` over the SAME block
        layout, top-1 as ZAYA routes; an expert nobody chose holds no
        block and a block of ONE real row computes its 15 pads as zeros.
        Float32: the tiles' sums differ from one product in order only."""
        monkeypatch.setattr(G, "TILE_WIDTH", 32)
        rng = np.random.default_rng(44)
        t, hidden = 40, 32 * tiles
        f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
        wg, wu, wd = f(E, M, hidden), f(E, M, hidden), f(E, hidden, M)
        x = jnp.asarray(rng.standard_normal((t, M)), jnp.float32)
        idx = rng.integers(0, E, (t, 1))
        if case == "nobody_chose_3":
            idx[idx == 3] = 4
        elif case == "one_row":
            idx[idx == 6] = 5
            idx[7] = 6                         # expert 6: one pair alone
        idx = jnp.asarray(idx, jnp.int32)
        w = jnp.asarray(rng.uniform(0.1, 1.0, (t, 1)), jnp.float32)
        held = jnp.ones((t, 1), bool)
        dest, be, nb, counts = G.plan(idx, held, E)
        rows = be.shape[0] * G.BLOCK_ROWS
        at = np.asarray(dest)[None, :, 0] == np.arange(rows)[:, None]
        x_rows = jnp.asarray(at, jnp.float32) @ x
        w_rows = jnp.asarray(at, jnp.float32) @ w[:, 0]
        want = G._ffn_xla(x_rows, w_rows, be, nb, wg, wu, wd)
        got = G._ffn_pallas(x_rows, w_rows, be, nb, wg, wu, wd,
                            interpret=True)
        assert rel(got, want) < 1e-5
        live = int(nb) * G.BLOCK_ROWS
        assert not np.asarray(got[live:]).any()    # blocks not in use
        assert not np.asarray(got)[~at.any(axis=1)].any()   # a block's pads
        if case == "nobody_chose_3":
            assert counts[3] == 0 and 3 not in np.asarray(be[:int(nb)])
        if case == "one_row":
            assert counts[6] == 1
        every, _ = _held_experts.raw_fn(x, idx, w, 0, wg, wu, wd)
        y, *_ = G.grouped_swiglu(x, idx, w, held, wg, wu, wd, interpret=True)
        assert rel(y, every) < 1e-5

    def test_tiled_kernel_refuses_a_training_batch(self, monkeypatch):
        monkeypatch.setattr(G, "TILE_WIDTH", 32)
        monkeypatch.setattr(G, "ACC_BYTES", 1024)
        z = jnp.zeros
        with pytest.raises(NotImplementedError, match="partial sums"):
            G._ffn_pallas(z((32, M)), z(32), z(2, jnp.int32),
                          jnp.asarray(2, jnp.int32), z((E, M, 64)),
                          z((E, M, 64)), z((E, 64, M)), interpret=True)

    def test_two_halves_of_the_experts_add_up(self):
        """The grouped product of a share (first > 0): a pair that falls
        on an expert held elsewhere gets no row here."""
        rng = np.random.default_rng(38)
        t = 21
        x = jnp.asarray(rng.standard_normal((t, M)), jnp.float32)
        wg, wu, wd = weights(rng)
        idx, w = routing(rng, t, "even")
        want, _ = _held_experts.raw_fn(x, idx, w, 0, wg, wu, wd)
        total, held = 0.0, 0
        for first in (0, 4):
            part = slice(first, first + 4)
            y, st = _grouped_experts.raw_fn(x, idx, w, None, first,
                                            wg[part], wu[part], wd[part])
            total, held = total + y, held + int(st[1])
        assert rel(total, want) < 1e-5 and held == t * K

    def test_plan_keeps_every_pair_in_its_expert_s_blocks(self):
        rng = np.random.default_rng(35)
        t = 50
        idx, _ = routing(rng, t, "skewed")
        held = jnp.asarray(rng.uniform(size=(t, K)) < 0.8)
        dest, block_expert, n_blocks, counts = G.plan(idx, held, E)
        dest, idx_np, held_np = (np.asarray(a) for a in (dest, idx, held))
        assert block_expert.shape[0] == G.plan_blocks(t * K, E)
        assert (dest[~held_np] == -1).all()
        rows = dest[held_np]
        assert len(set(rows.tolist())) == rows.size     # one row a pair
        assert rows.max() < int(n_blocks) * G.BLOCK_ROWS
        assert (np.asarray(block_expert)[rows // G.BLOCK_ROWS]
                == idx_np[held_np]).all()
        assert np.array_equal(
            np.asarray(counts),
            np.bincount(idx_np[held_np], minlength=E))

    def test_blocks_bound_holds_under_any_routing(self):
        for pairs, experts in [(64, 256), (2176, 256), (16, 8), (1, 4)]:
            worst = min(experts, pairs)
            # one pair on all but one touched expert, the rest on one
            n = [1] * (worst - 1) + [pairs - (worst - 1)]
            need = sum(-(-v // G.BLOCK_ROWS) for v in n)
            assert need <= G.plan_blocks(pairs, experts)


class TestSharesAddUp:
    def _layer(self, held, seed):
        paddle.seed(seed)
        gate = SigmoidTopKGate(M, E, topk=K, routed_scaling_factor=2.5)
        shared = paddle.nn.Linear(M, M, bias_attr=False)
        return MoELayer(M, SwiGLUExperts(held[1], M, H), gate=gate,
                        held_experts=held, shared_expert=shared)

    def test_shares_of_one_layer_sum_to_the_whole(self):
        """(0, 8) whole, by the grouped product (count > DENSE_SHARE x
        top_k);
        (0, 4) + (4, 4) and (0, 2) ... (6, 2) by the dense one: each
        family adds up to the whole with the shared expert counted
        once."""
        rng = np.random.default_rng(36)
        x = paddle.to_tensor(rng.standard_normal((19, M)).astype("float32"))
        whole = self._layer((0, E), 7)
        y_whole = whole(x)._data
        y_shared = whole.shared_expert(x)._data
        assert int(whole.routing_counts()["held"]) == 19 * K
        for count in (4, 2):
            total = 0.0
            for first in range(0, E, count):
                part = self._layer((first, count), 7)
                for name in ("gate_proj", "up_proj", "down_proj"):
                    getattr(part.experts, name)._data = getattr(
                        whole.experts, name)._data[first:first + count]
                part.gate.gate_weight._data = whole.gate.gate_weight._data
                part.shared_expert.weight._data = \
                    whole.shared_expert.weight._data
                total = total + (part(x)._data - y_shared)
            assert rel(total + y_shared, y_whole) < 1e-5

    @pytest.mark.parametrize("tokens", [3, 7, 8, 40])
    def test_a_share_on_the_line_takes_the_dense_product_at_any_step(
            self, monkeypatch, tokens):
        """4 held of 8 at top-2 sits ON the line (count == DENSE_SHARE x
        top_k) and takes the dense product whatever the step's tokens, a
        step so sparse that some held expert is chosen by nobody (3 tokens:
        6 pairs over 8 experts) included (ISSUE 49: measured at 16 of 256,
        where the grouped product won only at a 32-token step no cell
        runs).  The rule reads shapes, never the step."""
        from paddle_tpu.incubate.distributed.models.moe import moe_layer
        took = []
        for name in ("_held_experts", "_grouped_experts"):
            real = getattr(moe_layer, name)
            monkeypatch.setattr(
                moe_layer, name,
                lambda *a, _r=real, _n=name: took.append(_n) or _r(*a))
        rng = np.random.default_rng(49)
        layer = self._layer((4, 4), 9)
        x = paddle.to_tensor(rng.standard_normal((tokens, M))
                             .astype("float32"))
        y = layer(x)._data
        assert took == ["_held_experts"]
        ex = layer.experts
        idx, w = layer.gate.route_no_drop(x)
        want, *_ = G.grouped_swiglu(
            x._data, idx._data - 4, w._data,
            (idx._data >= 4) & (idx._data < 8), ex.gate_proj._data,
            ex.up_proj._data, ex.down_proj._data)
        assert rel(y, want + layer.shared_expert(x)._data) < 1e-5

    @pytest.mark.parametrize("held", [(0, E), (0, 4)])
    def test_token_mask_reaches_either_product(self, held):
        """The grouped product and the dense one of a narrow share leave
        the same named counts, of the real tokens only."""
        rng = np.random.default_rng(37)
        layer = self._layer(held, 8)
        x = paddle.to_tensor(rng.standard_normal((12, M)).astype("float32"))
        masked = layer(x, token_mask=jnp.arange(12) < 5)._data
        got = {n: int(v) for n, v in layer.routing_counts().items()}
        assert tuple(got) == ROUTING_FIELDS
        assert got["slots"] == 5 * K and got["held"] <= got["slots"]
        assert (got["held"] == got["slots"]) == (held[1] == E)
        assert 0 < got["touched"] <= held[1] and got["most"] <= 5
        # a real token's output does not know of the mask
        assert rel(masked[:5], layer(x)._data[:5]) < 1e-6
