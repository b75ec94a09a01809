"""Every Pallas kernel, compiled by the TPU's own compiler for a described
``v5e:2x2`` chip — the one file of chip compiles (rehearsal 3 of the
on-chip-measurement guide).

The compiler is installed in the sandbox and compiles for a chip that is
described, not attached: it refuses what the chip would refuse (a block
that does not fit scoped VMEM, an op Mosaic's verifier rejects, a slice
off the tiling), which interpret mode never sees.  Each case compiles
the kernel itself at the shapes the repo runs — the ``llama_7b`` widths
of ``chip_smoke.py`` and the LLaMA-110M geometry of ``bench.py`` — and
asserts a Mosaic ``tpu_custom_call`` in the compiled program, so a
silent fall-through to an XLA path cannot pass.  Nothing runs: results
are checked in interpret mode elsewhere and on the chip by the smoke.

The topology, the shardings and the mesh are built inside module-scoped
fixtures, never at import: only one process may load the TPU's library,
and every xdist worker imports this file.
"""
import collections
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from paddle_tpu.ops.pallas.flash_attention import (
    flash_attention_backward,
    flash_attention_forward,
)
from paddle_tpu.ops.pallas.flashmask_attention import (
    flashmask_attention_backward,
    flashmask_attention_forward,
)
from paddle_tpu.ops.pallas.fused_norm_rope import (
    fused_rope_pallas,
    rms_norm_pallas,
)
from paddle_tpu.ops.pallas import moe_grouped_ffn
from paddle_tpu.ops.pallas.moe_gating import topk_gating_pallas
from paddle_tpu.ops.pallas import paged_attention
from paddle_tpu.ops.pallas.paged_attention import (_decode_pallas,
                                                   append_rows)
from paddle_tpu.ops.pallas.quant_matmul import (
    w8a8_matmul_pallas,
    weight_only_matmul_pallas,
)

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8

# llama_7b widths (chip_smoke.py) and the LLaMA-110M geometry (bench.py)
H7, D7, HID7, FFN7 = 32, 128, 4096, 11008
B, H, KVH, S, D = 2, 12, 4, 1024, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tensor_mesh(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("tensor",))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep these silent."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def chip(one_chip, no_compile_cache):
    """``chip.compile(fn, (shape, dtype), ...)``: compile ``fn`` for the
    described chip; assert Mosaic went in; return the program text."""
    class Chip:
        @staticmethod
        def sds(shape, dtype=BF16, sharding=one_chip):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=sharding)

        def compile(self, fn, *specs):
            args = [s if isinstance(s, jax.ShapeDtypeStruct)
                    else self.sds(*s) for s in specs]
            text = jax.jit(fn).lower(*args).compile().as_text()
            assert "tpu_custom_call" in text, (
                "no Mosaic custom call in the compiled program — the "
                "Pallas path was not taken")
            return text

    return Chip()


def _paged_specs(chip, *, kvh, heads, d, batch, pages, page, table, nq=1,
                 int8=False, ragged=False, sharding=None, pool_sharding=None):
    """Argument specs of ``_decode_pallas`` in call order."""
    kw = {} if sharding is None else {"sharding": sharding}
    pkw = kw if pool_sharding is None else {"sharding": pool_sharding}
    q = (batch, heads, d) if nq == 1 else (batch, nq, heads, d)
    pool = (kvh, pages, page, d)
    specs = [chip.sds(q, BF16, **kw),
             chip.sds(pool, I8 if int8 else BF16, **pkw),
             chip.sds(pool, I8 if int8 else BF16, **pkw),
             chip.sds((batch,), I32, **kw),
             chip.sds((batch, table), I32, **kw)]
    if int8:
        specs += [chip.sds((kvh, pages, page, 1), F32, **pkw)] * 2
    if ragged:
        specs += [chip.sds((batch,), I32, **kw)]
    return specs


def _paged_fn(d, *, nq=1, int8=False, ragged=False, window=None):
    scale = 1.0 / math.sqrt(d)

    def fn(q, kp, vp, lens, tabs, *rest):
        kw = {}
        if int8:
            kw["k_scales"], kw["v_scales"] = rest[0], rest[1]
        if ragged:
            kw["q_lens"] = rest[-1]
        return _decode_pallas(q, kp, vp, lens, tabs, scale,
                              interpret=False, n_query=nq, window=window,
                              **kw)
    return fn


# ----------------------------------------------------- paged attention
class TestPagedAttentionLowering:
    def test_decode_110m(self, chip):
        chip.compile(_paged_fn(D), *_paged_specs(
            chip, kvh=KVH, heads=H, d=D, batch=8, pages=256, page=16,
            table=16))

    @pytest.mark.parametrize("kvh", [32, 8], ids=["mha", "gqa32_8"])
    def test_decode_llama_7b(self, chip, kvh):
        chip.compile(_paged_fn(D7), *_paged_specs(
            chip, kvh=kvh, heads=H7, d=D7, batch=8, pages=2056, page=16,
            table=256))

    @pytest.mark.parametrize("kvh,nq", [(32, 128), (8, 64)],
                             ids=["mha_chunk128", "gqa32_8_span64"])
    def test_ragged_bf16_llama_7b(self, chip, kvh, nq):
        chip.compile(_paged_fn(D7, nq=nq, ragged=True), *_paged_specs(
            chip, kvh=kvh, heads=H7, d=D7, batch=8, pages=2056, page=16,
            table=256, nq=nq, ragged=True))

    @pytest.mark.parametrize("page", [16, 32])
    def test_ragged_int8_llama_7b(self, chip, page):
        chip.compile(
            _paged_fn(D7, nq=128, int8=True, ragged=True), *_paged_specs(
                chip, kvh=32, heads=H7, d=D7, batch=8, pages=1024,
                page=page, table=128, nq=128, int8=True, ragged=True))

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("nq", [1, 64, 128],
                             ids=["decode", "span64", "span128"])
    def test_benchmark_cell_mistral_7b(self, chip, nq, int8):
        # `mistral7b.serve.closed8`'s own programs: 8 rows, 32/8 heads of
        # 128, a table pinned at 256 over 4,096 pages of 16.  The block
        # of the walk is sized from these shapes: one that overruns
        # scoped VMEM fails here, before it fails on the chip
        chip.compile(
            _paged_fn(D7, nq=nq, int8=int8, ragged=nq > 1), *_paged_specs(
                chip, kvh=8, heads=H7, d=D7, batch=8, pages=4096, page=16,
                table=256, nq=nq, int8=int8, ragged=nq > 1))

    @pytest.mark.parametrize("nq", [256, 512], ids=["span256", "span512"])
    def test_spans_past_the_score_block_budget(self, chip, nq):
        # rows = span x 4: the block shrinks (256 tokens, then 128) so the
        # float32 score block stays at 1 MB
        chip.compile(_paged_fn(D7, nq=nq, ragged=True), *_paged_specs(
            chip, kvh=8, heads=H7, d=D7, batch=2, pages=4096, page=16,
            table=256, nq=nq, ragged=True))

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("page", [8, 32, 64, 128])
    def test_page_sizes_other_than_16(self, chip, page, int8):
        # pool tiling is (16,128) for bf16 and (32,128) for int8: a page
        # that is not a whole tile must still compile
        chip.compile(_paged_fn(D7, int8=int8), *_paged_specs(
            chip, kvh=32, heads=H7, d=D7, batch=8, pages=512, page=page,
            table=32, int8=int8))

    @pytest.mark.parametrize("kvh,nq,ragged",
                             [(32, 3, False), (8, 5, False), (32, 2, True)],
                             ids=["rows3", "rows20", "rows2_ragged"])
    def test_query_rows_not_a_multiple_of_8(self, chip, kvh, nq, ragged):
        # rows = n_query * group rides as the q block's sublane dim
        chip.compile(_paged_fn(D7, nq=nq, ragged=ragged), *_paged_specs(
            chip, kvh=kvh, heads=H7, d=D7, batch=8, pages=512, page=16,
            table=32, nq=nq, ragged=ragged))

    def test_ragged_under_shard_map_on_four_chips(self, chip, tensor_mesh):
        """The TP serving layout: q and the pools sharded on the (kv-)head
        axis of a 4-device mesh of the described chips, the ragged kernel
        per shard inside ``shard_map``."""
        from jax import shard_map
        rep = NamedSharding(tensor_mesh, P())
        heads = NamedSharding(tensor_mesh, P(None, None, "tensor", None))
        pool = NamedSharding(tensor_mesh, P("tensor"))
        kernel = _paged_fn(D7, nq=128, ragged=True)
        fn = shard_map(
            kernel, mesh=tensor_mesh,
            in_specs=(P(None, None, "tensor", None), P("tensor"),
                      P("tensor"), P(), P(), P()),
            out_specs=P(None, None, "tensor", None), check_vma=False)
        text = chip.compile(
            fn, chip.sds((8, 128, H7, D7), BF16, sharding=heads),
            chip.sds((32, 2056, 16, D7), BF16, sharding=pool),
            chip.sds((32, 2056, 16, D7), BF16, sharding=pool),
            chip.sds((8,), I32, sharding=rep),
            chip.sds((8, 256), I32, sharding=rep),
            chip.sds((8,), I32, sharding=rep))
        # per chip: a quarter of the heads — no gather of the pools
        assert "all-gather" not in text


class TestHeadGroupLowering:
    """A grid step owns a row and a group of its kv heads (ISSUE 42): the
    page copy with the pool's head axis strided, the (hb, rows, lanes)
    blocks and scratch and a head index read from a loop counter, at the
    shapes `phi4-flash.serve.reason32` hands the kernels (32 rows, ten
    pair-heads of 128 lanes at group 4, a table of 256 over 6,144 pages)
    and with the group forced to a part of the heads and to one."""

    @pytest.mark.parametrize("nq", [1, 128], ids=["decode", "span128"])
    @pytest.mark.parametrize("window", [None, 512], ids=["full", "sliding"])
    def test_phi4_flash_cell(self, chip, window, nq):
        assert paged_attention.walk_head_group(
            10, 16, 128, nq * 4, BF16, BF16) == 10
        text = chip.compile(
            _paged_fn(128, nq=nq, ragged=nq > 1, window=window),
            *_paged_specs(chip, kvh=10, heads=40, d=128, batch=32,
                          pages=6144, page=16, table=256, nq=nq,
                          ragged=nq > 1))
        # the q operand: a block a row of all ten heads for the one-query
        # kernel, the packed tokens kv-head-major for the ragged one
        assert ("bf16[32,10,4,128]" if nq == 1
                else "bf16[10,4096,4,128]") in text

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("hb", [1, 2, 8])
    def test_a_part_of_the_heads(self, chip, hb, int8):
        def fn(q, kp, vp, lens, tabs, *rest):
            kw = {"q_lens": rest[-1]}
            if int8:
                kw.update(k_scales=rest[0], v_scales=rest[1])
            return paged_attention._decode_call(
                q, kp, vp, lens, tabs, 0.1, n_query=128, head_group=hb,
                **kw)

        chip.compile(fn, *_paged_specs(
            chip, kvh=8, heads=H7, d=D7, batch=8, pages=4096, page=16,
            table=256, nq=128, int8=int8, ragged=True))


# ------------------------------------------------------- the KV append
class TestAppendRowsLowering:
    """One layer of the serving step at the benchmark cell's shapes
    (ISSUE 30): ``append_rows`` for K and V, then the ragged kernel, the
    pools donated.  The compiled program must hold each pool in ONE
    layout from the jit boundary to the Pallas calls and back out: the
    only instructions that produce a pool-sized array are the appends
    themselves, which write in place — the ``kv_append_rows`` kernel for
    a pool whose pages are whole tiles (bf16), the row scatter's fusion
    for one whose are not (int8 pages of 16 slots).  (Indexing
    ``pool[:, pg, sl]`` compiled to two pool-sized ``copy`` a pool: the
    scatter's window held the kv-head axis, so XLA re-laid the pool out
    and back.)"""

    KVH, PAGES, PAGE, ROWS, SPAN, TABLE = 8, 4096, 16, 8, 128, 256

    @staticmethod
    def _pool_sized(text, count, dtype):
        """{opcode: n} of the instructions whose result is ``dtype`` with
        ``count`` elements."""
        ops = collections.Counter()
        for dt, dims, op in re.findall(
                r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                r"([\w\-]+)\(", text, re.M):
            if dt == dtype and math.prod(
                    int(d) for d in dims.split(",") if d) == count:
                ops[op] += 1
        return ops

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    def test_no_pool_sized_copy_in_a_layer(self, chip, monkeypatch, int8):
        # ``_use_pallas`` asks the default backend, which is the CPU here
        monkeypatch.setattr(paged_attention, "_use_pallas", lambda: True)
        tokens = self.ROWS * self.SPAN
        pdt = I8 if int8 else BF16
        pool = chip.sds((self.KVH, self.PAGES, self.PAGE, D7), pdt)
        scale_pool = chip.sds((self.KVH, self.PAGES, self.PAGE, 1), F32)
        pools = [pool, pool] + ([scale_pool, scale_pool] if int8 else [])
        new = [chip.sds((self.KVH, tokens, D7), pdt)] * 2 + (
            [chip.sds((self.KVH, tokens, 1), F32)] * 2 if int8 else [])
        kernel = _paged_fn(D7, nq=self.SPAN, int8=int8, ragged=True)

        n = len(pools)

        def layer(*args):
            pg, sl = args[n:n + 2]
            q, lens, tabs, q_lens = args[2 * n + 2:]
            out = [append_rows(p, pg, sl, v)
                   for p, v in zip(args[:n], args[n + 2:2 * n + 2])]
            return (kernel(q, out[0], out[1], lens, tabs, *out[2:], q_lens),
                    *out)

        text = jax.jit(layer, donate_argnums=tuple(range(n))).lower(
            *pools, chip.sds((tokens,), I32), chip.sds((tokens,), I32), *new,
            chip.sds((self.ROWS, self.SPAN, H7, D7), BF16),
            chip.sds((self.ROWS,), I32),
            chip.sds((self.ROWS, self.TABLE), I32),
            chip.sds((self.ROWS,), I32)).compile().as_text()
        assert "tpu_custom_call" in text
        assert ("kv_append_rows" in text) == (not int8)
        # every pool is updated in the buffer it arrived in
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        aliased = {int(p) for p in re.findall(r"\((\d+), \{\}",
                                              alias.group(1))}
        assert aliased >= set(range(n)), alias.group(0)
        ops = self._pool_sized(
            text, self.KVH * self.PAGES * self.PAGE * D7,
            "s8" if int8 else "bf16")
        if int8:
            # the two scatter fusions, and the ConcatBitcast custom call
            # through which XLA stages the K pool for the kernel whatever
            # form the append takes; the ``[..., 1]`` scale pools (2 MB)
            # are still re-laid out around their scatter (PERF.md
            # section 7) and are not counted here
            assert ops["scatter"] == 2 and ops["fusion"] == 2, ops
            allowed = {"parameter", "bitcast", "scatter", "fusion",
                       "custom-call"}
        else:
            assert ops["custom-call"] == 2, ops     # K's append and V's
            allowed = {"parameter", "bitcast", "custom-call"}
        assert set(ops) <= allowed, (
            f"a pool-sized array is produced by {set(ops) - allowed}: the "
            f"pool changes layout inside the step ({dict(ops)})")


# ----------------------------------------------------- flash attention
def _flash_bwd(causal, d):
    scale = 1.0 / math.sqrt(d)

    def fn(q, k, v, out, lse, do):
        return flash_attention_backward(q, k, v, out, lse, do, causal,
                                        scale, interpret=False)
    return fn


class TestFlashAttentionLowering:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward(self, chip, causal):
        fn = functools.partial(flash_attention_forward, causal=causal,
                               interpret=False)
        chip.compile(fn, *[((B, H, S, D),)] * 3)

    def test_forward_gqa(self, chip):
        fn = functools.partial(flash_attention_forward, causal=True,
                               interpret=False)
        chip.compile(fn, ((B, H, S, D),), ((B, KVH, S, D),),
                     ((B, KVH, S, D),))

    def test_forward_unaligned_seq(self, chip):
        # 1000 tokens: exercises the pad-to-block path under Mosaic
        fn = functools.partial(flash_attention_forward, causal=True,
                               interpret=False)
        chip.compile(fn, *[((B, H, 1000, D),)] * 3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward(self, chip, causal):
        chip.compile(_flash_bwd(causal, D), *[((B, H, S, D),)] * 4,
                     ((B, H, S), F32), ((B, H, S, D),))

    def test_backward_gqa(self, chip):
        chip.compile(_flash_bwd(True, D), ((B, H, S, D),),
                     ((B, KVH, S, D),), ((B, KVH, S, D),), ((B, H, S, D),),
                     ((B, H, S), F32), ((B, H, S, D),))

    @pytest.mark.parametrize("kvh", [32, 8], ids=["mha", "gqa32_8"])
    def test_forward_and_backward_llama_7b(self, chip, kvh):
        q, kv = ((2, H7, 2048, D7),), ((2, kvh, 2048, D7),)
        fwd = functools.partial(flash_attention_forward, causal=True,
                                interpret=False)
        chip.compile(fwd, q, kv, kv)
        chip.compile(_flash_bwd(True, D7), q, kv, kv, q,
                     ((2, H7, 2048), F32), q)

    def test_forward_and_backward_latent_attention_widths(self, chip):
        """Kimi-Linear's MLA at the cell's size: 192-wide scores (128
        nope + 64 shared), 128-wide values, 32 heads, 8,192 tokens."""
        qk, v = ((1, 32, 8192, 192),), ((1, 32, 8192, 128),)
        fwd = functools.partial(flash_attention_forward, causal=True,
                                scale=192 ** -0.5, interpret=False)
        chip.compile(fwd, qk, qk, v)

        def bwd(q, k, v, out, lse, do):
            return flash_attention_backward(q, k, v, out, lse, do, True,
                                            192 ** -0.5, interpret=False)
        chip.compile(bwd, qk, qk, v, v, ((1, 32, 8192), F32), v)

    @pytest.mark.parametrize("qk,kv,v,tiles", [
        ((1, 32, 4096, 128), (1, 8, 4096, 128), (1, 8, 4096, 128), 10),
        ((1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128), 36),
    ], ids=["mistral7b.train.seq4k", "kimi-linear.train.seq8k"])
    def test_the_training_cells_at_the_swept_blocks(self, chip, qk, kv, v,
                                                    tiles):
        """Both cells' calls with no block named: the sweep's 1,024 x
        1,024 lowers for the chip, forward and both backward kernels, and
        a head's schedule is the lower triangle's tiles."""
        from paddle_tpu import monitor
        visited = monitor.counter("flash_attn_tiles_visited_total")
        scale, out = qk[-1] ** -0.5, qk[:3] + v[-1:]
        before = visited.value()
        chip.compile(functools.partial(flash_attention_forward, causal=True,
                                       scale=scale, interpret=False),
                     (qk,), (kv,), (v,))
        assert visited.value() - before == 32 * tiles

        def bwd(q, k, v, out, lse, do):
            return flash_attention_backward(q, k, v, out, lse, do, True,
                                            scale, interpret=False)
        chip.compile(bwd, (qk,), (kv,), (v,), (out,), (qk[:3], F32), (out,))
        assert visited.value() - before == 3 * 32 * tiles

    def test_compiled_program_carries_the_kernel_payload(self, chip):
        fn = functools.partial(flash_attention_forward, causal=True,
                               interpret=False)
        text = chip.compile(fn, *[((B, H, S, D),)] * 3)
        # a real kernel at these shapes is tens of KB of serialized MLIR
        assert len(text) > 10_000


class TestKernelNames:
    """The four kernels of the main paths carry their ``name=``: the
    compiled instruction is named after it, and a device trace shows
    each custom call under that name (benchmark ``breakdown.device_ops``,
    ``kernel.*`` metrics)."""

    @pytest.fixture(scope="class")
    def programs(self, chip):
        fwd = functools.partial(flash_attention_forward, causal=True,
                                interpret=False)
        bwd = chip.compile(_flash_bwd(True, D), *[((B, H, S, D),)] * 4,
                           ((B, H, S), F32), ((B, H, S, D),))
        return {
            "paged_attention_ragged": chip.compile(
                _paged_fn(D7, nq=8, ragged=True), *_paged_specs(
                    chip, kvh=8, heads=H7, d=D7, batch=8, pages=512,
                    page=16, table=64, nq=8, ragged=True)),
            "flash_attention_fwd": chip.compile(fwd,
                                                *[((B, H, S, D),)] * 3),
            "flash_attention_bwd_dkv": bwd,
            "flash_attention_bwd_dq": bwd,
        }

    @pytest.mark.parametrize("name", [
        "paged_attention_ragged", "flash_attention_fwd",
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq"])
    def test_instruction_is_named_after_the_kernel(self, programs, name):
        calls = [ln for ln in programs[name].splitlines()
                 if "tpu_custom_call" in ln and f"%{name}" in ln]
        assert calls, f"no custom call named {name}"
        assert f"/{name}/pallas_call" in calls[0]      # and its op_name


class TestFlashMaskLowering:
    @pytest.mark.parametrize("ncol", [1, 2, 4])
    def test_forward(self, chip, ncol):
        def fn(q, k, v, se):
            return flashmask_attention_forward(q, k, v, se, causal=True,
                                               interpret=False)

        chip.compile(fn, *[((B, H, S, D),)] * 3, ((B, 1, S, ncol), I32))

    def test_backward(self, chip):
        def fn(q, k, v, out, lse, do, se):
            return flashmask_attention_backward(
                q, k, v, out, lse, do, se, causal=True, interpret=False)

        chip.compile(fn, *[((B, H, S, D),)] * 4, ((B, H, S), F32),
                     ((B, H, S, D),), ((B, 1, S, 2), I32))

    def test_forward_and_backward_head_dim_128(self, chip):
        q = ((2, H7, 2048, D7),)

        def fwd(q_, k, v, se):
            return flashmask_attention_forward(q_, k, v, se, causal=True,
                                               interpret=False)

        def bwd(q_, k, v, out, lse, do, se):
            return flashmask_attention_backward(
                q_, k, v, out, lse, do, se, causal=True, interpret=False)

        chip.compile(fwd, q, q, q, ((2, 1, 2048, 2), I32))
        chip.compile(bwd, q, q, q, q, ((2, H7, 2048), F32), q,
                     ((2, 1, 2048, 2), I32))


# ------------------------------------------------------- rmsnorm + rope
class TestFusedNormRopeLowering:
    def test_rmsnorm(self, chip):
        fn = functools.partial(rms_norm_pallas, interpret=False)
        chip.compile(fn, ((B * S, 768),), ((768,),))

    def test_rmsnorm_3d_f32(self, chip):
        fn = functools.partial(rms_norm_pallas, interpret=False)
        chip.compile(fn, ((B, S, 768), F32), ((768,), F32))

    def test_rmsnorm_llama_7b(self, chip):
        fn = functools.partial(rms_norm_pallas, interpret=False)
        chip.compile(fn, ((2, 2048, HID7),), ((HID7,),))

    def test_rope(self, chip):
        fn = functools.partial(fused_rope_pallas, interpret=False)
        chip.compile(fn, ((B, S, H, D),), ((B, S, KVH, D),),
                     ((S, D // 2), F32), ((S, D // 2), F32))

    @pytest.mark.parametrize("kvh,dtype,seq",
                             [(32, BF16, 2048), (8, BF16, 2048),
                              (32, F32, 2048), (32, BF16, 1)],
                             ids=["mha_bf16", "gqa32_8_bf16", "mha_f32",
                                  "one_token"])
    def test_rope_llama_7b(self, chip, kvh, dtype, seq):
        # 32 heads x 128 at block_s=512 asked 32 MB of scoped VMEM against
        # a 16 MB limit; the block is sized from the shape now
        fn = functools.partial(fused_rope_pallas, interpret=False)
        chip.compile(fn, ((2, seq, H7, D7), dtype),
                     ((2, seq, kvh, D7), dtype),
                     ((seq, D7 // 2), F32), ((seq, D7 // 2), F32))


# ------------------------------------------------------------ MoE gating
class TestMoEGatingLowering:
    @pytest.mark.parametrize("top_k", [1, 2])
    def test_gating(self, chip, top_k):
        # tpu.iota takes integers only: the float iota failed Mosaic's
        # verifier until _argmax_rows converted after
        fn = functools.partial(topk_gating_pallas, top_k=top_k,
                               capacity=128, normalize=True,
                               interpret=False)
        chip.compile(fn, ((4096, 64), F32))


# ---------------------------------------------------------- int8 matmuls
class TestQuantMatmulLowering:
    @pytest.mark.parametrize("shape", [(1, 768, 2048),      # decode step
                                       (8192, 768, 32000),  # lm head
                                       (8, HID7, FFN7),     # 7b up-proj
                                       (1024, FFN7, HID7)],  # 7b down-proj
                             ids=str)
    def test_weight_only_matmul(self, chip, shape):
        m, k, n = shape
        chip.compile(
            functools.partial(weight_only_matmul_pallas, interpret=False),
            ((m, k),), ((k, n), I8), ((n,), F32))

    @pytest.mark.parametrize("shape", [(8, HID7, FFN7), (1024, FFN7, HID7)],
                             ids=str)
    def test_w8a8_matmul(self, chip, shape):
        m, k, n = shape
        chip.compile(
            functools.partial(w8a8_matmul_pallas, out_dtype=BF16,
                              interpret=False),
            ((m, k), I8), ((m, 1), F32), ((k, n), I8), ((n,), F32))


# ------------------------------------- the Laguna serving cell's kernels
class TestLagunaCellLowering:
    """`laguna-xs2.serve.agent8`'s own kernels at its shapes: 8 rows, 48
    or 64 query heads over 8 KV heads of 128, a table pinned at 512 over
    8,192 pages of 16, a window of 512 in the 64-head layers; 256 experts
    of 2,048 x 512, 8 a token, at a decode step's 8 positions and a chunk
    step's 272."""

    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("nq", [1, 128], ids=["decode", "span128"])
    @pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                             ids=["full48", "sliding64"])
    def test_paged_kernels(self, chip, heads, window, nq, int8):
        chip.compile(
            _paged_fn(128, nq=nq, int8=int8, ragged=nq > 1, window=window),
            *_paged_specs(chip, kvh=8, heads=heads, d=128, batch=8,
                          pages=8192, page=16, table=512, nq=nq, int8=int8,
                          ragged=nq > 1))

    @pytest.mark.parametrize("nq", [16, 32, 64, 128])
    @pytest.mark.parametrize("heads,window,kvh", [
        (32, None, 8), (48, None, 8), (64, 512, 8)],
        ids=["mistral32", "full48", "sliding64"])
    def test_ragged_query_tiles(self, chip, heads, window, kvh, nq):
        """The ragged kernel's query tiles at every span the two serving
        cells' mixes can ask for: 512, 768 and 1,024 rows at span 128 in
        tiles of 128, 96 and 128 rows, sliced out of the q block, the
        scratch and the output at a traced row offset."""
        group = heads // kvh
        tile = paged_attention.query_tile_rows(nq * group, group, BF16)
        assert tile == min(nq * group, 96 if group == 6 else 128)
        text = chip.compile(
            _paged_fn(128, nq=nq, ragged=True, window=window),
            *_paged_specs(chip, kvh=kvh, heads=heads, d=128, batch=8,
                          pages=8192, page=16, table=512, nq=nq,
                          ragged=True))
        assert "paged_attention_ragged" in text

    @pytest.mark.parametrize("tokens,e,h,k", [
        (8, 256, 512, 8), (272, 256, 512, 8),
        (64, 16, 2048, 1), (192, 16, 2048, 1)],
        ids=["laguna8", "laguna272", "zaya64", "zaya192"])
    def test_grouped_experts(self, chip, monkeypatch, tokens, e, h, k):
        """Laguna's 256 experts of 2,048 x 512 (one tile: a step a block)
        and ZAYA's 16 of 2,048 x 2,048 (four tiles of 512 and a float32
        accumulator: whole, 50.3 MB of weight buffers would not fit
        VMEM), at a decode step's positions and a chunk step's."""
        monkeypatch.setattr(moe_grouped_ffn, "_use_pallas", lambda: True)
        m = 2048
        text = chip.compile(
            moe_grouped_ffn.grouped_swiglu, ((tokens, m),),
            ((tokens, k), I32), ((tokens, k), F32),
            ((tokens, k), jnp.bool_), ((e, m, h),), ((e, m, h),),
            ((e, h, m),))
        calls = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "%moe_grouped_ffn" in ln]
        assert calls, "no custom call named moe_grouped_ffn"
        # the experts' weights reach the kernel as they are stored
        assert not re.search(
            rf"bf16\[{e},\d+,\d+\][^ ]* (copy|transpose)\(", text)


class TestMiMoCellLowering:
    """`mimo-v2-flash.serve.mixed32`'s own kernels at its shapes: 32 rows,
    64 query heads over 8 KV heads (window 128, a sink a query head) or 4
    (full), K heads of 192 lying two to a row of 384 beside V pages of 128,
    a table pinned at 512 over 8,192 pages of 16; 16 held experts of
    4,096 x 2,048 at top-8 (the dense product in the cell; the grouped
    one, which ``chip_limits_mimo.py --experts`` times against it, lowers
    at a decode step's 32 positions, where the float32 partial sums of
    every row fit, and is refused in words at a chunk step's 160 and 288,
    where they do not)."""

    @pytest.mark.parametrize("nq", [1, 32, 128],
                             ids=["decode", "span32", "span128"])
    @pytest.mark.parametrize("kvh,window,sinks", [(8, 128, True),
                                                  (4, None, False)],
                             ids=["sliding8", "full4"])
    def test_paged_kernels(self, chip, kvh, window, sinks, nq):
        heads, dk, dv, batch = 64, 192, 128, 32
        assert paged_attention.k_pack(dk) == 2

        def fn(q, kp, vp, lens, tabs, ql, b):
            return _decode_pallas(
                q, kp, vp, lens, tabs, 1 / math.sqrt(dk), n_query=nq,
                q_lens=ql if nq > 1 else None, window=window,
                sinks=b if sinks else None)

        q = (batch, heads, dk) if nq == 1 else (batch, nq, heads, dk)
        text = chip.compile(
            fn, (q,), ((kvh // 2, 8192, 16, 2 * dk),),
            ((kvh, 8192, 16, dv),), ((batch,), I32), ((batch, 512), I32),
            ((batch,), I32), ((heads,), F32))
        assert ("paged_attention_ragged" if nq > 1
                else "paged_attention") in text
        # neither pool is copied or re-laid out on the way to the kernel
        assert not re.search(
            r"bf16\[\d+,8192,16,\d+\][^ ]* (copy|transpose|pad)\(", text)

    @pytest.mark.parametrize(
        "heads,kvh,window,dk,sinks,nq,hb,block,before", [
            (48, 8, None, 128, False, 128, 8, 512, 256),
            (64, 8, 512, 128, False, 128, 8, 512, 256),
            (64, 4, None, 192, False, 64, 4, 512, 256),
            (64, 4, None, 192, False, 128, 4, 512, 128),
            (64, 8, 128, 192, True, 64, 8, 256, 512)],
        ids=["laguna-full", "laguna-sliding", "mimo-full-span64",
             "mimo-full", "mimo-sliding-span64"])
    def test_blocks_cut_by_the_tile(self, chip, heads, kvh, window, dk,
                                    sinks, nq, hb, block, before):
        """The five ragged programs whose walk ISSUE 51 cuts anew, at
        their cells' shapes: the score tile's rows (128, 96 at Laguna's
        group of 6) allow blocks of 512 tokens where the bucket's rows
        allowed ``before`` — K and V buffers of 32 pages for every head of
        the grid step, beside the bucket's stages and scratch, are what
        Mosaic is asked to fit — and MiMo's window of 128 reaches no
        further than 256."""
        dv, group, batch = 128, heads // kvh, 8
        pack = paged_attention.k_pack(dk)
        tile, pages, got = paged_attention.walk_cut(
            kvh, 16, dk, nq, group, BF16, BF16, dv, sinks, window=window)
        assert (tile, 16 * pages, got) == (96 if group == 6 else 128, block,
                                           hb)
        assert 16 * paged_attention.walk_block_pages(
            16, dk, nq * group, BF16, dv) == before

        def fn(q, kp, vp, lens, tabs, ql, b):
            return _decode_pallas(
                q, kp, vp, lens, tabs, 1 / math.sqrt(dk), n_query=nq,
                q_lens=ql, window=window, sinks=b if sinks else None)

        text = chip.compile(
            fn, ((batch, nq, heads, dk),),
            ((kvh // pack, 8192, 16, pack * dk),), ((kvh, 8192, 16, dv),),
            ((batch,), I32), ((batch, 512), I32), ((batch,), I32),
            ((heads,), F32))
        assert "paged_attention_ragged" in text

    def test_the_append_takes_a_packed_k_pool(self, chip, monkeypatch):
        monkeypatch.setattr(paged_attention, "_use_pallas", lambda: True)
        text = chip.compile(
            lambda pool, pg, sl, vals: append_rows(pool, pg, sl, vals),
            ((4, 8192, 16, 384),), ((160,), I32), ((160,), I32),
            ((4, 160, 384),))
        assert "kv_append_rows" in text

    @pytest.mark.parametrize("tokens", [32, 160, 288])
    def test_grouped_experts(self, chip, monkeypatch, tokens):
        monkeypatch.setattr(moe_grouped_ffn, "_use_pallas", lambda: True)
        m, h, e, k = 4096, 2048, 16, 8
        rows = moe_grouped_ffn.plan_blocks(tokens * k, e) \
            * moe_grouped_ffn.BLOCK_ROWS
        fits = rows * m * 4 <= moe_grouped_ffn.ACC_BYTES
        assert fits == (tokens == 32)
        shapes = (((tokens, m),), ((tokens, k), I32), ((tokens, k), F32),
                  ((tokens, k), jnp.bool_), ((e, m, h),), ((e, m, h),),
                  ((e, h, m),))
        if not fits:
            with pytest.raises(NotImplementedError, match="partial sums"):
                chip.compile(moe_grouped_ffn.grouped_swiglu, *shapes)
            return
        text = chip.compile(moe_grouped_ffn.grouped_swiglu, *shapes)
        assert [ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and "%moe_grouped_ffn" in ln]
        assert not re.search(
            rf"bf16\[{e},\d+,\d+\][^ ]* (copy|transpose)\(", text)


# ------------------- the ragged kernel on the step's packed tokens
class TestPackedRaggedLowering:
    """The ragged kernel as the serving step calls it (ISSUE 50): the
    queries of the step's PACKED tokens in, each row's own from
    ``row_off`` on, at every serving cell's (rows, span, query heads, KV
    heads, K and V widths) and the tokens its engine packs a step to."""

    # name: rows, tokens, q heads, kv heads, K width, V width, window,
    # sinks, pages, table
    CELLS = {
        "mistral": (8, 272, 32, 8, 128, 128, None, False, 4096, 256),
        "laguna_full": (8, 272, 48, 8, 128, 128, None, False, 8192, 512),
        "laguna_sliding": (8, 272, 64, 8, 128, 128, 512, False, 8192, 512),
        "phi4_flash_full": (32, 288, 40, 10, 128, 128, None, False, 6144,
                            256),
        "phi4_flash_sliding": (32, 288, 40, 10, 128, 128, 512, False, 6144,
                               256),
        "zaya": (64, 320, 8, 2, 128, 128, None, False, 12288, 512),
        "mimo_full": (32, 288, 64, 4, 192, 128, None, False, 8192, 512),
        "mimo_sliding": (32, 288, 64, 8, 192, 128, 128, True, 8192, 512),
    }
    SPAN = 128

    def _compile(self, chip, monkeypatch, name):
        (rows, tokens, heads, kvh, dk, dv, window, sinks, pages,
         table) = self.CELLS[name]
        monkeypatch.setattr(paged_attention, "_use_pallas", lambda: True)
        pack = paged_attention.k_pack(dk)

        def fn(q, kp, vp, lens, ql, off, tabs, b):
            # what ``_TracedPagedContext._attend_ragged`` does
            return paged_attention.paged_attention_ragged(
                paged_attention.packed_queries(q, kp, vp), kp, vp, lens, ql,
                tabs, window=window, sinks=b if sinks else None,
                row_off=off, span=self.SPAN)

        return chip.compile(
            fn, ((tokens, heads, dk),),
            ((kvh // pack, pages, 16, pack * dk),), ((kvh, pages, 16, dv),),
            ((rows,), I32), ((rows,), I32), ((rows,), I32),
            ((rows, table), I32), ((heads,), F32))

    @pytest.mark.parametrize("name", sorted(CELLS))
    def test_each_serving_cell_s_call(self, chip, monkeypatch, name):
        text = self._compile(chip, monkeypatch, name)
        assert "paged_attention_ragged" in text
        # no pool is copied or re-laid out on the way to the kernel
        pages = self.CELLS[name][8]
        assert not re.search(
            rf"bf16\[\d+,{pages},16,\d+\][^ ]* (copy|transpose|pad)\(", text)

    @pytest.mark.parametrize("name", ["mimo_full", "mimo_sliding"])
    def test_no_array_of_the_rectangle_s_size(self, chip, monkeypatch, name):
        """The MiMo cell's call: 288 packed tokens of a (32, 128) bucket.
        Nothing around the kernel is as large as the 4,096 positions of
        the rectangle, in any of the layouts it used to pass through: the
        largest array beside the pools is the step's own tokens."""
        text = self._compile(chip, monkeypatch, name)
        rows, tokens, heads, kvh = self.CELLS[name][:4]
        for shape in (f"[{rows},{self.SPAN},{heads},", f"[{rows * self.SPAN},",
                      f"[{rows},{kvh},", f"[{kvh},{rows * self.SPAN},"):
            assert f"bf16{shape}" not in text, shape
        sizes = [math.prod(int(n) for n in dims.split(","))
                 for dims in re.findall(r"bf16\[([\d,]+)\]", text)]
        pools = 8192 * 16 * 128
        assert max(n for n in sizes if n < pools) <= \
            (tokens + 15) * heads * 384


# ------------------------------------ the Kimi-Linear cell's KDA kernels
class TestKdaChunkLowering:
    """`kimi-linear.train.seq8k`'s chunkwise delta rule at its shape,
    1 x 8,192 tokens, 32 heads of 128, every stream in the rows the
    kernels read ([B, T, H * d]: PR 46): the forward and the backward
    kernel behind one ``custom_vjp``, as the traced step calls them; and
    the mixer's XLA half around them, which must reach the kernels
    without re-laying a stream out."""

    ROWS = (1, 8192, 32 * 128)
    SHAPES = ((ROWS, BF16),) * 3 + ((ROWS, F32), ((1, 8192, 32), F32))

    def test_forward(self, chip):
        from paddle_tpu.ops.pallas.kda_chunk import kda_chunk_pallas
        text = chip.compile(lambda *xs: kda_chunk_pallas(*xs), *self.SHAPES)
        assert "%kda_chunk_fwd" in text and "%kda_chunk_bwd" not in text

    def test_forward_and_backward_stay_under_the_callers_scope(self, chip):
        from paddle_tpu.ops.pallas.kda_chunk import kda_chunk_pallas

        def loss(*xs):
            with jax.named_scope("train/model"), jax.named_scope("kda"):
                o, _ = kda_chunk_pallas(*xs)
            return jnp.sum(o.astype(F32) ** 2)

        text = chip.compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                            *self.SHAPES)
        # `train.device.kda` finds an instruction by the scope in its
        # op_name: both kernels must carry the forward call site's
        for name in ("kda_chunk_fwd", "kda_chunk_bwd"):
            calls = [ln for ln in text.splitlines()
                     if "tpu_custom_call" in ln and f"%{name}" in ln]
            assert calls, f"no custom call named {name}"
            for ln in calls:
                op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
                assert "train/model" in op_name and "/kda/" in op_name, ln

    def test_the_mixers_streams_reach_the_kernels_as_they_are_made(
            self, chip, monkeypatch):
        """Gates, kernels and the gated head norm, forward and backward:
        the compiled program holds no instruction of its own that only
        moves a stream (a `copy`, `transpose`, `reshape` or `broadcast`
        of 8,192 x 4,096 elements, which is what the [B, T, H, d] views
        cost: nine `copy f32[1024,8,32,128]` a layer before PR 46) — a
        per-head reduction is a fusion over the rows' own tiles."""
        from paddle_tpu.models import kimi_linear as KL
        from paddle_tpu.ops import kda
        from paddle_tpu.ops.pallas import kda_chunk as kc
        monkeypatch.setattr(kc, "supported", lambda dk, dv: True)

        def loss(q, k, v, f, gate, b_logits, a_log, dt_bias, weight):
            q, k, a, beta = KL._kda_gates.raw_fn(q, k, f, a_log, dt_bias,
                                                 b_logits, 32)
            o, _ = kda.kda_chunk_rows.raw_fn(q, k, v, a, beta)
            y = KL._gated_head_rms_norm.raw_fn(o, gate, weight, 1e-5)
            return jnp.sum(y.astype(F32) ** 2)

        text = chip.compile(
            jax.grad(loss, argnums=tuple(range(9))),
            *((self.ROWS, BF16),) * 5, ((1, 8192, 32), BF16),
            ((32,), F32), ((4096,), F32), ((128,), BF16))
        assert "%kda_chunk_fwd" in text and "%kda_chunk_bwd" in text
        entry = text[text.index("ENTRY"):]
        mover = re.compile(r" = (?:bf16|f32)\[([\d,]*)\]\S* "
                           r"(?:copy|transpose|reshape|broadcast)\(")
        moved = [ln.strip()[:120] for ln in entry.splitlines()
                 if (m := mover.search(ln)) and math.prod(
                     int(n) for n in m.group(1).split(",") if n)
                 >= 8192 * 4096]
        assert not moved, moved


# ------------------------------- the Brumby cell's retention state kernels
class TestRetentionStateLowering:
    """`brumby-14b.serve.reason16`'s retention layer at its shapes: 16 + 1
    slots of 8 KV heads x [136, 8320] float32 (580 MB a layer), 40 query
    heads of 128, a ragged step of 16 rows.  One layer's
    ``retention_step`` with the pool donated: the one-token kernel alone
    (span 1), and beside the chunk kernel at (16, 128) packed to 272
    positions.  The only instructions that produce a pool-sized array are
    the kernels themselves, which write in place."""

    SLOTS, KVH, HEADS, ROWS = 16, 8, 40, 16

    @pytest.mark.parametrize("span, tokens, chunk_rows",
                             [(1, 16, 0), (128, 272, 2)],
                             ids=["decode", "chunk"])
    def test_a_layer_updates_its_pool_in_place(self, chip, monkeypatch,
                                               span, tokens, chunk_rows):
        from paddle_tpu.ops import power_retention as pr
        monkeypatch.setattr(pr, "_use_pallas", lambda: True)
        pool = (self.SLOTS + 1,) + pr.state_shape(self.KVH, D7, D7)
        packed = tokens < self.ROWS * span

        def layer(pool, slots, ctx, q_lens, off, rows, q, k, v, log_g):
            return pr.retention_step(pool, slots, ctx, q_lens,
                                     off if packed else None, rows, q, k, v,
                                     log_g, span=span)

        rows = chip.sds((self.ROWS,), I32)
        text = jax.jit(layer, donate_argnums=0).lower(
            chip.sds(pool, F32), rows, rows, rows, rows,
            chip.sds((chunk_rows,), I32),
            chip.sds((tokens, self.HEADS, D7)),
            chip.sds((tokens, self.KVH, D7)),
            chip.sds((tokens, self.KVH, D7)),
            chip.sds((tokens, self.KVH), F32)).compile().as_text()
        assert "%retention_decode" in text
        assert ("%retention_chunk" in text) == bool(chunk_rows)
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert "(0, {}" in alias.group(1), alias.group(0)
        ops = TestAppendRowsLowering._pool_sized(text, math.prod(pool),
                                                 "f32")
        assert set(ops) <= {"parameter", "get-tuple-element", "bitcast",
                            "custom-call"}, (
            f"a pool-sized array is produced by {dict(ops)}: the slots "
            "are copied inside the step")


# --------------------------- the Phi-4-mini-flash cell's Mamba state kernel
class TestSelectiveScanLowering:
    """`phi4-flash.serve.reason32`'s Mamba layer at its shapes: 32 + 1
    slots of ``h`` [16, 5120] and a tail [3 x 5120] float32, a ragged step
    of 32 rows.  One layer's ``conv_step`` + ``scan_step`` with both pools
    donated: the one-token rows alone (span 1), and beside a chunk row at
    (32, 128) packed to 160 positions.  ``h``'s pool is produced by the
    kernel alone, which writes in place.  (In a program this small the
    compiler may stage the whole 10.8 MB pool in VMEM around the kernels
    and copy it back, ``copy-done``: a placement, which the whole step's
    program, where nine pools and the weights want that memory, makes for
    none of its pools in the decode program and one of nine in the chunk
    program: PERF.md section 6, PR 41.  No scatter, fusion or plain copy
    of the pool may appear.)"""

    SLOTS, ROWS, N, D, K = 32, 32, 16, 5120, 4

    @pytest.mark.parametrize("span, tokens, chunk_rows",
                             [(1, 32, 0), (128, 160, 2), (256, 288, 2)],
                             ids=["decode", "chunk", "chunk256"])
    def test_a_layer_updates_its_pools_in_place(self, chip, monkeypatch,
                                                span, tokens, chunk_rows):
        from paddle_tpu.ops import selective_scan as ss
        monkeypatch.setattr(ss, "_use_pallas", lambda: True)
        h_pool = (self.SLOTS + 1, self.N, self.D)
        packed = tokens < self.ROWS * span

        def layer(h, tail, slots, ctx, q_lens, off, rows, x, w, b, delta, a,
                  bb, cc, d):
            off = off if packed else None
            u, tail = ss.conv_step(tail, slots, ctx, q_lens, off, x, w, b,
                                   span=span)
            m, h = ss.scan_step(h, slots, ctx, q_lens, off, rows, u, delta,
                                a, bb, cc, d, span=span)
            return m, h, tail

        rows = chip.sds((self.ROWS,), I32)
        text = jax.jit(layer, donate_argnums=(0, 1)).lower(
            chip.sds(h_pool, F32),
            chip.sds((self.SLOTS + 1, (self.K - 1) * self.D), F32),
            rows, rows, rows, rows, chip.sds((chunk_rows,), I32),
            chip.sds((tokens, self.D)), chip.sds((self.K, self.D)),
            chip.sds((self.D,)), chip.sds((tokens, self.D), F32),
            chip.sds((self.N, self.D), F32), chip.sds((tokens, self.N)),
            chip.sds((tokens, self.N)), chip.sds((self.D,), F32)
        ).compile().as_text()
        assert text.count("tpu_custom_call") >= (2 if chunk_rows else 1)
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        assert "(0, {}" in alias.group(1), alias.group(0)
        ops = TestAppendRowsLowering._pool_sized(text, math.prod(h_pool),
                                                 "f32")
        assert set(ops) <= {"parameter", "get-tuple-element", "bitcast",
                            "custom-call", "copy-done"}, (
            f"a pool-sized array is produced by {dict(ops)}: the slots "
            "are copied inside the step")
