"""The chunkwise delta rule of ``ops/kda.py`` as two Pallas kernels: a
chunk's working set stays in VMEM.

``ops/kda.py``'s docstring is the mathematics and the numerics (the
cumulative log-decay, the 16-row sub-block rule that never forms
exp(-g), T = (I + A)^-1 by forward substitution in float32, matmul
operands in the inputs' dtype with float32 accumulation); this file is
where they run on a TPU.  HBM sees the op's inputs, its output and, for
the backward, the state that enters each chunk; ``col``, ``pair``, the
diagonal sums and the blocks of the inverse never leave the chip.  A
stream is read and written as the rows a projection leaves it in,
[B, T, H * d]: a grid step's block is 64 tokens of ``hb`` heads' lanes,
and ``kda_chunk_pallas`` takes and returns such rows, so nothing around
the kernels re-lays a stream out.

* ``kda_chunk_fwd``: grid (batch, head group, chunk), the chunk axis
  sequential; the state of each head of the group is carried from chunk
  to chunk in VMEM scratch, TRANSPOSED ([dv, dk]: the decay of a chunk is
  a vector over dk, which then scales lanes and needs no transpose).
* ``kda_chunk_bwd``: the same grid walked from the last chunk to the
  first with dS carried the same way; a chunk's intermediates are
  recomputed in VMEM from its inputs and its incoming state (one state a
  chunk is what the forward keeps for it), the inverse's gradient is
  dN = -T^T dT T^T, and the decay products' gradient follows the same
  sub-block rule: with E = exp(g_r - g_i), R_x[r] = sum_i dX[r, i] k_i E
  and C[i] = sum_r (dKK[r, i] k_r + dQK[r, i] q_r) E give
  dk = R_k + C, dq = R_q and dg = k R_k + q R_q - k C.

Within a 16 x 16 diagonal block both kernels walk the columns j = 0..15:
column j's decay factors exp(g_r - g_j), r >= j, are one [64, dk] pass
for the four blocks of a chunk (from column 8 on over the lower eight
rows of each block only), its sums over dk are column j of KK and QK,
and column j of N is at once one step of the forward substitution
(X <- X - N[:, j] X[j, :], right-looking), so the triangular solve costs
no pass of its own.  The 16 -> 32 -> 64 levels are
D <- D - D C D with C the blocks of N that join two solved halves.

What the schedule of the compiled kernel (bundles a grid step, read off
the compiler's own dump: PERF.md section 6, PR 34) made of it: the
cumulative sums are three bfloat16 passes of a 0/1 matrix; and the heads
of a grid step are written a stage at a time, head after head
(``_heads_in_turn``), because each head's tail is a chain of products
that wait for each other and the compiler keeps close to the order it is
given.  And what tracing made of it: the column walks are functions of
values alone, ``jax.jit``-ed inside the kernels (``_front``,
``_pairs_grad``), so that their thousands of operations are traced once
a process and not once a head of each kernel.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16
NB = CHUNK // SUB
F32 = jnp.float32
_HI = lax.Precision.HIGHEST
# heads that share a grid step: from the device trace at the cell's shape
# ([1, 8192, 32, 128]), see PERF.md section 6, PR 34
HEADS_PER_STEP = 4


def _nn(a, b, precision=None):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           precision=precision, preferred_element_type=F32)


def _nt(a, b, precision=None):
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=precision, preferred_element_type=F32)


def _tn(a, b, precision=None):
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           precision=precision, preferred_element_type=F32)


def _sum_rows(which, x):
    """which [C, C] of zeros and ones, x [C, n] float32 -> which @ x as
    exact as a float32 sum: x is cut into three bfloat16 pieces that add
    up to it (the ones are exact as they are), three passes of the MXU
    where a float32 product at the highest precision takes six."""
    bf = jnp.bfloat16
    which = which.astype(bf)
    hi = x.astype(bf)
    rest = x - hi.astype(F32)
    mid = rest.astype(bf)
    low = (rest - mid.astype(F32)).astype(bf)
    return _nn(which, hi) + _nn(which, mid) + _nn(which, low)


def _masks(dk):
    """Index patterns of a [C, C] block and of a chunk's rows."""
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    # a [C, 1] pattern costs a relayout at each use: whole vregs
    rows = lax.broadcasted_iota(jnp.int32, (CHUNK, dk), 0)
    return SimpleNamespace(
        lower=row >= lane, strict=row > lane, upper=row <= lane,
        # a lane's place in the diagonal block of its row
        rel=lane - (row & ~(SUB - 1)),
        join32=((row >> 5) == (lane >> 5)) & ((row >> 4) != (lane >> 4)),
        join64=(row >> 5) != (lane >> 5),
        rows=rows, rmod=rows & (SUB - 1),
        eye_wide=(lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 0)
                  == lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 1)
                  ).astype(F32))


def _first_live_row(j):
    """Column j of a diagonal block meets the rows from j on: from
    column 8 on the first eight rows of every block (one vreg each) hold
    nothing, and the column's pass leaves them out."""
    return 8 if j >= 8 else 0


def _live(x, lo):
    """x [C, n] -> the rows lo..15 of each block, [4 (16 - lo), n]."""
    if not lo:
        return x
    return jnp.concatenate([x[b * SUB + lo:(b + 1) * SUB]
                            for b in range(NB)], axis=0)


def _whole(x, lo):
    """``_live``'s inverse, zeros in the rows it left out."""
    if not lo:
        return x
    n = SUB - lo
    zero = jnp.zeros((lo, x.shape[-1]), x.dtype)
    return jnp.concatenate(
        [y for b in range(NB) for y in (zero, x[b * n:(b + 1) * n])], axis=0)


def _row_of_each_block(x, j, lo=0):
    """x [C, n] -> as many rows as ``_live`` keeps, those of block b
    all x[16 b + j]."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[b * SUB + j:b * SUB + j + 1, :],
                          (SUB - lo, x.shape[-1])) for b in range(NB)],
        axis=0)


def _column_of_each_block(x, j, n, lo=0):
    """x [C, C] -> [rows ``_live`` keeps, n]: every lane of row r holds
    x[r, 16 b + j], b the row's block."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[b * SUB + lo:(b + 1) * SUB,
                            b * SUB + j:b * SUB + j + 1], (SUB - lo, n))
         for b in range(NB)], axis=0)


def _column_decay(m, g, j):
    """exp(g_r - g_j) for column j of every live row's own diagonal
    block: the rows from j on (g falls along a chunk, so the exponent is
    <= 0), zero in the rows above.  g [C, dk] -> [live rows, dk]."""
    lo = _first_live_row(j)
    return jnp.exp(jnp.where(
        _live(m.rmod, lo) >= j,
        _live(g, lo) - _row_of_each_block(g, j, lo), -jnp.inf))


def _off_diagonal(m, n, qf, kf, g, cd):
    """Row block n against every earlier column, decays taken from the
    cumulative decay just before the block (both factors <= 1)."""
    lo, hi = n * SUB, (n + 1) * SUB
    ref = g[lo - 1:lo, :]                                     # [1, dk]
    rowf = jnp.exp(g[lo:hi] - ref)                            # [16, dk]
    colf = jnp.exp(jnp.where(m.rows < lo, ref - g, -jnp.inf))  # [C, dk]
    x = jnp.concatenate([kf[lo:hi] * rowf, qf[lo:hi] * rowf],
                        axis=0).astype(cd)                    # [32, dk]
    return rowf, colf, x, (kf * colf).astype(cd)


@jax.jit
def _front(q, k, v, a, beta):
    """A chunk's decay products and the forward substitution of its four
    16 x 16 diagonal blocks: the vector and cross-lane part of
    ``ops/kda.py::_prepare``; ``_finish`` is the chain of matrix products
    that follows.  A function of values alone and ``jax.jit``-ed: the
    sixteen columns are some 1,500 operations to trace, every head of
    every kernel calls this with the same shapes, and so it is traced
    once a process and not twelve times (which cost the cell 17 s of
    ``setup_s``: PERF.md section 6, PR 34)."""
    cd = q.dtype
    m = _masks(k.shape[-1])
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    # [C, 1] -> whole vregs: a [C, 1] operand costs a relayout at each use
    beta_k, beta_v = (jnp.broadcast_to(beta, x.shape) for x in (kf, vf))
    g = _sum_rows(m.lower, a)                                 # cumsum
    zero = jnp.zeros((SUB, CHUNK), F32)
    kk_rows, qk_rows = [zero], [zero]
    for n in range(1, NB):
        _, _, x, k_col = _off_diagonal(m, n, qf, kf, g, cd)
        blk = _nt(x, k_col)                                   # [32, C]
        kk_rows.append(blk[:SUB])
        qk_rows.append(blk[SUB:])
    kk = jnp.concatenate(kk_rows, axis=0)
    qk = jnp.concatenate(qk_rows, axis=0)
    # the diagonal blocks column by column, and the forward substitution
    # of the 16 x 16 blocks with them
    x_val = m.eye_wide                                        # [C, 128]
    for j in range(SUB):
        lo = _first_live_row(j)
        t = _row_of_each_block(kf, j, lo) * _column_decay(m, g, j)
        # float32 sums over dk, pair by pair as the XLA path has them, in
        # every lane of their row: a [n, 1] result costs a relayout at
        # each use
        wide = (t.shape[0], 128)
        w_k = jnp.broadcast_to(
            jnp.sum(_live(kf, lo) * t, axis=1, keepdims=True), wide)
        w_q = jnp.broadcast_to(
            jnp.sum(_live(qf, lo) * t, axis=1, keepdims=True), wide)
        here = m.rel == j
        kk = jnp.where(here, _whole(w_k, lo)[:, :CHUNK], kk)
        qk = jnp.where(here, _whole(w_q, lo)[:, :CHUNK], qk)
        if j < SUB - 1:
            col = jnp.where(_live(m.rmod[:, :128], lo) > j,
                            _live(beta_k[:, :128], lo) * w_k, 0.0)
            x_val = x_val - _whole(col * _row_of_each_block(x_val, j, lo), lo)
    return dict(qf=qf, kf=kf, vf=vf, g=g, kk=kk, qk=qk, beta_k=beta_k,
                beta_v=beta_v, g_end=g[CHUNK - 1:CHUNK, :],
                t16=x_val[:, :CHUNK])


def _finish(m, f):
    """From ``_front``'s products to what ``ops/kda.py::_prepare``
    returns (and what the backward reuses): the inverse's upper levels,
    W_k, W_v, P, Q+, K~ and exp(g_C).  A generator, for
    ``_heads_in_turn``: it yields where the next product waits for the
    last, and returns the results (``p = yield from _finish(m, f)``)."""
    cd, qf, kf, g = f.cd, f.qf, f.kf, f.g
    n_mat = jnp.where(m.strict, f.beta_k[:, :CHUNK] * f.kk, 0.0)
    t_inv = f.t16
    for join in (m.join32, m.join64):
        inner = _nn(jnp.where(join, n_mat, 0.0), t_inv, _HI)
        yield
        t_inv = t_inv - _nn(t_inv, inner, _HI)
        yield
    decay = jnp.exp(g)
    to_end = jnp.exp(f.g_end - g)
    k_plus = (kf * decay * f.beta_k).astype(cd)
    v_beta = (f.vf * f.beta_v).astype(cd)
    t_cd = t_inv.astype(cd)
    p = SimpleNamespace(
        cd=cd, qf=qf, kf=kf, vf=f.vf, g=g, kk=f.kk, t_inv=t_inv, t_cd=t_cd,
        decay=decay, to_end=to_end, gamma=jnp.exp(f.g_end),
        k_plus=k_plus, v_beta=v_beta,
        w_k=_nn(t_cd, k_plus).astype(cd), w_v=_nn(t_cd, v_beta),
        p=f.qk.astype(cd), q_plus=(qf * decay).astype(cd),
        k_tilde=(kf * to_end).astype(cd))
    yield
    return p


@jax.jit
def _pairs_grad(q, k, g, d_kk, d_p):
    """The decay products' gradient from dKK and dQK [C, C]: R_k and R_q
    by rows, C by columns (the module docstring), by the sub-block rule
    of the forward.  ``jax.jit``-ed for the reason ``_front`` is."""
    cd, dk = q.dtype, k.shape[-1]
    m = _masks(dk)
    qf, kf = q.astype(F32), k.astype(F32)
    zero = jnp.zeros((SUB, dk), F32)
    rk_rows, rq_rows = [zero], [zero]
    ck = jnp.zeros((CHUNK, dk), F32)
    for n in range(1, NB):
        lo, hi = n * SUB, (n + 1) * SUB
        rowf, colf, x, k_col = _off_diagonal(m, n, qf, kf, g, cd)
        d_blk = jnp.concatenate([d_kk[lo:hi], d_p[lo:hi]],
                                axis=0).astype(cd)            # [32, C]
        dx = _nn(d_blk, k_col)                                # [32, dk]
        rk_rows.append(dx[:SUB] * rowf)
        rq_rows.append(dx[SUB:] * rowf)
        ck = ck + _tn(d_blk, x) * colf
    rk = jnp.concatenate(rk_rows, axis=0)
    rq = jnp.concatenate(rq_rows, axis=0)
    for j in range(SUB):
        lo = _first_live_row(j)
        e = _column_decay(m, g, j)
        t = _row_of_each_block(kf, j, lo) * e
        d_kk_j = _column_of_each_block(d_kk, j, dk, lo)       # [live, dk]
        d_qk_j = _column_of_each_block(d_p, j, dk, lo)
        rk = rk + _whole(d_kk_j * t, lo)
        rq = rq + _whole(d_qk_j * t, lo)
        # column j's own gradient: its block's rows added up, into row j
        col = (d_kk_j * _live(kf, lo) + d_qk_j * _live(qf, lo)) * e
        n = SUB - lo
        sums = jnp.concatenate(
            [jnp.broadcast_to(jnp.sum(col[b * n:(b + 1) * n], axis=0,
                                      keepdims=True), (SUB, dk))
             for b in range(NB)], axis=0)
        ck = ck + jnp.where(m.rmod == j, sums, 0.0)
    return rk, rq, ck


def _heads_in_turn(heads, front, rest):
    """Every head's ``front(h)``, then the generators ``rest(h, front)``
    a stage at a time, head after head.  The heads share nothing; a rest
    is a chain of matrix products that wait for each other, the compiler
    keeps close to the order it is given, and in this order one head's
    products run while another's are on their way."""
    done = object()
    chains = [rest(h, front(h)) for h in range(heads)]
    while chains:
        chains = [c for c in chains if next(c, done) is not done]


def _head_fronts(inputs, dk, dv):
    """(cut, front): a head's lanes of the k-wide and the v-wide streams,
    and its ``_front`` from the step's input blocks."""
    q_ref, k_ref, v_ref, a_ref, beta_ref = inputs

    def cut(h):
        return slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)

    def front(h):
        ks, vs = cut(h)
        q = q_ref[0, :, ks]
        return SimpleNamespace(q=q, k=k_ref[0, :, ks], cd=q.dtype, **_front(
            q, k_ref[0, :, ks], v_ref[0, :, vs], a_ref[0, :, ks],
            beta_ref[0, h]))

    return cut, front


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, s0_ref, *refs,
                heads, dk, dv, save_states):
    if save_states:
        o_ref, s_end_ref, states_ref = refs[:3]
    else:
        (o_ref, s_end_ref), states_ref = refs[:2], None
    st_scr = refs[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _first():
        st_scr[...] = s0_ref[0]

    m = _masks(dk)
    cut, front = _head_fronts((q_ref, k_ref, v_ref, a_ref, beta_ref), dk, dv)

    def rest(h, f):
        st = st_scr[h]                                        # [dv, dk]
        if save_states:
            states_ref[0, h, 0] = st
        p = yield from _finish(m, f)
        st_cd = st.astype(p.cd)
        u = (p.w_v - _nt(p.w_k, st_cd)).astype(p.cd)
        yield
        o = _nt(p.q_plus, st_cd) + _nn(p.p, u)
        o_ref[0, :, cut(h)[1]] = o.astype(o_ref.dtype)
        st_scr[h] = st * p.gamma + _tn(u, p.k_tilde)

    _heads_in_turn(heads, front, rest)

    @pl.when(c == pl.num_programs(2) - 1)
    def _last():
        s_end_ref[0] = st_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, states_ref, do_ref,
                ds_end_ref, dq_ref, dk_ref, dv_ref, da_ref, dbeta_ref,
                ds0_ref, dst_scr, *, heads, dk, dv):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _first():
        dst_scr[...] = ds_end_ref[0]

    m = _masks(dk)
    cut, front = _head_fronts((q_ref, k_ref, v_ref, a_ref, beta_ref), dk, dv)

    def rest(h, f):
        ks, vs = cut(h)
        p = yield from _finish(m, f)
        cd, qf, kf = p.cd, p.qf, p.kf
        st = states_ref[0, h, 0]                              # [dv, dk]
        dst_next = dst_scr[h]
        st_cd, dsn_cd = st.astype(cd), dst_next.astype(cd)
        do = do_ref[0, :, vs].astype(cd)
        u = (p.w_v - _nt(p.w_k, st_cd)).astype(cd)
        # o = Q+ S + P U,  S' = gamma S + K~^T U,  U = W_v - W_k S
        du = _tn(p.p, do) + _nt(p.k_tilde, dsn_cd)            # [C, dv]
        du_cd = du.astype(cd)
        yield
        d_p = jnp.where(m.lower, _nt(do, u), 0.0)             # [C, C]
        d_q_plus = _nn(do, st_cd)
        d_k_tilde = _nn(u, dsn_cd)
        d_gamma = jnp.sum(st * dst_next, axis=0, keepdims=True)
        d_w_k = (-_nn(du_cd, st_cd)).astype(cd)
        dst_scr[h] = (dst_next * p.gamma + _tn(do, p.q_plus)
                      - _tn(du_cd, p.w_k))
        yield
        # W_k = T (beta K+), W_v = T (beta V), T = (I + N)^-1
        d_t = _nt(d_w_k, p.k_plus) + _nt(du_cd, p.v_beta)
        d_k_plus = _tn(p.t_cd, d_w_k)
        d_v_beta = _tn(p.t_cd, du_cd)
        yield
        inner = _tn(p.t_inv, d_t, _HI)
        yield
        d_n = jnp.where(m.strict, -_nt(inner, p.t_inv, _HI), 0.0)
        yield
        d_kk = f.beta_k[:, :CHUNK] * d_n
        d_decay_k = d_k_plus * kf * p.decay
        dbeta_ref[0, h] = (
            jnp.sum(d_n * p.kk, axis=1, keepdims=True)
            + jnp.sum(d_decay_k, axis=1, keepdims=True)
            + jnp.sum(d_v_beta * p.vf, axis=1, keepdims=True))
        rk, rq, ck = _pairs_grad(f.q, f.k, p.g, d_kk, d_p)
        d_to_end = d_k_tilde * kf * p.to_end
        dq_ref[0, :, ks] = (rq + d_q_plus * p.decay).astype(dq_ref.dtype)
        dk_ref[0, :, ks] = (rk + ck + d_k_plus * p.decay * f.beta_k
                            + d_k_tilde * p.to_end).astype(dk_ref.dtype)
        dv_ref[0, :, vs] = (d_v_beta * f.beta_v).astype(dv_ref.dtype)
        dg = (kf * (rk - ck) + qf * rq + d_decay_k * f.beta_k
              + d_q_plus * qf * p.decay - d_to_end)
        dg_end = (jnp.sum(d_to_end, axis=0, keepdims=True)
                  + d_gamma * p.gamma)
        # g = cumsum(a): da[t] = sum of dg[r], r >= t; g_end is g's last row
        da_ref[0, :, ks] = _sum_rows(m.upper, dg) + dg_end

    _heads_in_turn(heads, front, rest)

    @pl.when(c == pl.num_programs(2) - 1)
    def _last():
        ds0_ref[0] = dst_scr[...]


def _layout(q, v, beta, order):
    """What both calls share: the grid (batch, head group, chunk), the
    block specs of a [B, T, H * D] stream, of beta [B, H, T, 1], of a
    state [B, H, dv, dk] and of the states [B, H, chunks, dv, dk], and
    the scratch a head's ``_front`` needs.  ``order`` maps the grid's
    chunk index to the chunk."""
    b, t, h = q.shape[0], q.shape[1], beta.shape[1]
    dk, dv = q.shape[2] // h, v.shape[2] // h
    hb = max(n for n in range(1, HEADS_PER_STEP + 1) if h % n == 0)
    n_chunks = t // CHUNK

    def stream(d):
        return pl.BlockSpec((1, CHUNK, hb * d),
                            lambda i, j, c: (i, order(c, n_chunks), j))

    return SimpleNamespace(
        kernel=dict(heads=hb, dk=dk, dv=dv), grid=(b, h // hb, n_chunks),
        k=stream(dk), v=stream(dv),
        beta=pl.BlockSpec((1, hb, CHUNK, 1),
                          lambda i, j, c: (i, j, order(c, n_chunks), 0)),
        state=pl.BlockSpec((1, hb, dv, dk), lambda i, j, c: (i, j, 0, 0)),
        states=pl.BlockSpec(
            (1, hb, 1, dv, dk),
            lambda i, j, c: (i, j, order(c, n_chunks), 0, 0)),
        state_shape=jax.ShapeDtypeStruct((b, h, dv, dk), F32),
        states_shape=jax.ShapeDtypeStruct((b, h, n_chunks, dv, dk), F32),
        # the state, or its gradient, carried from chunk to chunk
        scratch=[pltpu.VMEM((hb, dv, dk), F32)],
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


def _fwd_call(q, k, v, a, beta, s0, save_states, interpret):
    """q, k, a [B, T, H * dk], v [B, T, H * dv], beta [B, H, T, 1]
    float32, s0 [B, H, dv, dk] float32; T whole chunks."""
    lay = _layout(q, v, beta, lambda c, n: c)
    out_specs = [lay.v, lay.state]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype), lay.state_shape]
    if save_states:
        out_specs.append(lay.states)
        out_shape.append(lay.states_shape)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, save_states=save_states,
                          **lay.kernel),
        name="kda_chunk_fwd", grid=lay.grid,
        in_specs=[lay.k, lay.k, lay.v, lay.k, lay.beta, lay.state],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=lay.scratch,
        compiler_params=lay.params, interpret=interpret,
    )(q, k, v, a, beta, s0)


def _bwd_call(q, k, v, a, beta, states, do, ds_end, interpret):
    lay = _layout(q, v, beta, lambda c, n: n - 1 - c)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **lay.kernel),
        name="kda_chunk_bwd", grid=lay.grid,
        in_specs=[lay.k, lay.k, lay.v, lay.k, lay.beta, lay.states, lay.v,
                  lay.state],
        out_specs=[lay.k, lay.k, lay.v, lay.k, lay.beta, lay.state],
        out_shape=[like(q.shape, q.dtype), like(k.shape, k.dtype),
                   like(v.shape, v.dtype), like(a.shape, F32),
                   like(beta.shape, F32), lay.state_shape],
        scratch_shapes=lay.scratch,
        compiler_params=lay.params, interpret=interpret,
    )(q, k, v, a, beta, states, do, ds_end)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda(q, k, v, a, beta, s0, interpret):
    """The two kernels as one differentiable op, in their own layout
    (``_fwd_call``'s)."""
    return tuple(_fwd_call(q, k, v, a, beta, s0, False, interpret))


def _kda_fwd(q, k, v, a, beta, s0, interpret):
    o, s_end, states = _fwd_call(q, k, v, a, beta, s0, True, interpret)
    return (o, s_end), (q, k, v, a, beta, states)


def _kda_bwd(interpret, res, cot):
    return tuple(_bwd_call(*res, *cot, interpret))


_kda.defvjp(_kda_fwd, _kda_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk_pallas(q, k, v, a, beta, initial_state=None, interpret=False):
    """``ops/kda.py::kda_chunk_rows``'s arguments and results, in the
    kernels' own layout: q, k, a [B, T, H * dk], v [B, T, H * dv] as a
    projection writes them, beta [B, T, H], initial_state [B, H, dk, dv]
    or None; returns (o [B, T, H * dv], final state [B, H, dk, dv]
    float32).  No stream is reshaped on the way in or out.  dk 128, dv
    whole 128-lane tiles.  Differentiable in all six arguments.  T is
    padded to whole chunks: zero keys, values and betas and a zero
    log-decay leave the state as it is."""
    b, t, h = beta.shape
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    pad = -t % CHUNK

    def whole(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    beta = jnp.swapaxes(whole(beta.astype(F32)), 1, 2)        # [B, H, T]
    s0 = (jnp.zeros((b, h, dv, dk), F32) if initial_state is None
          else jnp.swapaxes(initial_state.astype(F32), -1, -2))
    o, s_end = _kda(whole(q), whole(k), whole(v), whole(a.astype(F32)),
                    beta[..., None], s0, interpret)
    return o[:, :t], jnp.swapaxes(s_end, -1, -2)


def supported(dk, dv):
    """What the kernels can take, from what the caller can see: a TPU,
    keys one 128-lane tile wide (the published head width; the TPU's
    compiler aborts on this kernel at two) and values of whole tiles."""
    return jax.default_backend() == "tpu" and dk == 128 and dv % 128 == 0
