"""Mamba-2 SSD chunked scan as Pallas TPU kernels: a forward and its backward.

TPU adaptation of the GPU selective scan: the chunked *dual form* turns the
recurrence into (Q x Q), (Q x N) and (N x P) matmuls per chunk (MXU work),
with only the inter-chunk state carried sequentially.  One program holds
every head of one (batch row, chunk): C.B^T is computed once for all heads
(one group of B and C), and each head's (Q x Q) decay matrix, its product
with C.B^T and their gradients live only in VMEM.  The carried state
(H*P x N) lives in VMEM scratch across the sequential chunk axis of the
grid, the Pallas analogue of the fused CUDA scan's register-resident state.

Layouts (the model's, with no transpose of x or y): x, y, dx (B, L, H*P);
dt (B, L, H) and its transpose (B, H, L); b, c (B, L, N); states
(B, H*P, N).  Grid (B, L/Q), the chunk axis sequential (reversed in the
backward).  Head ``h`` owns lanes [h P, (h+1) P): a 128-lane group holds
128 / P whole heads, so every block and slice obeys the (8, 128) tiling
rule; a head's (Q, P) product is taken over the group's 128 lanes and its
own lanes kept by a lane mask, which costs the MXU nothing at P = 64.  A
program loops over its groups (``lax.fori_loop``), so that each kernel's
body is one group long to trace and to compile.

Per-position decay quantities are needed both as columns (Q, H) and as
rows (H, Q): they come from ``dt`` and its transpose by the same float32
prefix sums, so the two agree bit for bit and no kernel transposes.

Precision: the cumulative sums, exponentials and every reduction run in
float32; every product is one bfloat16 pass with float32 accumulation (the
JAX default for float32 matmuls on the TPU, as the jnp scan computes them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

LANES = 128
_F32, _BF16 = jnp.float32, jnp.bfloat16
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _dot(a, b, dims=_NN):
    return lax.dot_general(a.astype(_BF16), b.astype(_BF16), dims,
                           preferred_element_type=_F32)


def _prefix_sum(v, axis: int, reverse: bool = False):
    """Inclusive (or, reversed, suffix) sum of float32 ``v`` along ``axis``,
    in log2(n) shifted adds."""
    n = v.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    s = 1
    while s < n:
        if reverse:   # v[i] += v[i + s]
            v = v + jnp.where(idx < n - s, pltpu.roll(v, n - s, axis), 0.0)
        else:         # v[i] += v[i - s]
            v = v + jnp.where(idx >= s, pltpu.roll(v, s, axis), 0.0)
        s *= 2
    return v


def _lane(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 1)


def _by_head(parts, p):
    """One (R, 128) group from per-head full-width values: head ``j``'s
    lanes [j P, (j+1) P) from ``parts[j]``."""
    out = parts[0]
    lane = _lane(out.shape)
    for j, part in enumerate(parts[1:], 1):
        out = jnp.where(lane >= j * p, part, out)
    return out


def _head_sums(z, p, heads, acc):
    """``acc`` (R, H) plus, in column ``heads[j]``, the sum of ``z`` (R, 128)
    over head j's lanes."""
    lane = _lane(z.shape)
    col = _lane(acc.shape)
    for j, h in enumerate(heads):
        own = (lane >= j * p) & (lane < (j + 1) * p)
        s = jnp.sum(jnp.where(own, z, 0.0), axis=1, keepdims=True)
        acc = jnp.where(col == h, acc + s, acc)
    return acc


class _Chunk:
    """What every program of either kernel needs of its chunk.  Per-head
    columns are laid over the heads' lanes once, into ``dtg_scr`` and
    ``csg_scr`` (Q, H*P), so that the loop over 128-lane groups slices them
    as it slices x."""

    def __init__(self, dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref,
                 dtg_scr, csg_scr, p):
        dt = dt_ref[0]                                 # (Q, H)
        self.q, self.nh = dt.shape
        self.p, self.k = p, LANES // p                 # heads a group
        cs = _prefix_sum(dt * a_ref[...], 0)           # (Q, H) cumulative
        self.cst = _prefix_sum(dtt_ref[0] * at_ref[...], 1)  # (H, Q) same
        for g in range(self.nh // self.k):
            heads = range(g * self.k, (g + 1) * self.k)
            cols = slice(g * LANES, (g + 1) * LANES)
            dtg_scr[:, cols] = _by_head([jnp.broadcast_to(
                dt[:, h:h + 1], (self.q, LANES)) for h in heads], p)
            csg_scr[:, cols] = _by_head([jnp.broadcast_to(
                cs[:, h:h + 1], (self.q, LANES)) for h in heads], p)
        self.dtg_scr, self.csg_scr = dtg_scr, csg_scr
        self.dt, self.cs = dt, cs
        self.bm, self.cm = b_ref[0], c_ref[0]          # (Q, N)
        self.scores = _dot(self.cm, self.bm, _NT)      # (Q, Q) C.B^T
        self.tril = (lax.broadcasted_iota(jnp.int32, (self.q, self.q), 0)
                     >= lax.broadcasted_iota(jnp.int32, (self.q, self.q), 1))
        self.head_row = lax.broadcasted_iota(jnp.int32, self.cst.shape, 0)

    def group(self, g):
        """Group g's lanes (and state rows), its heads, and its dt and
        cumulative log-decay laid over its lanes (Q, 128)."""
        cols = pl.ds(pl.multiple_of(g * LANES, LANES), LANES)
        heads = [g * self.k + j for j in range(self.k)]
        return cols, heads, self.dtg_scr[:, cols], self.csg_scr[:, cols]

    def decay(self, csg, j, h):
        """Head h's decay matrix L[i, j] = exp(cs_i - cs_j), zero for
        j > i; ``csg`` its group's cumulative log-decay, h its j-th head."""
        row = jnp.sum(jnp.where(self.head_row == h, self.cst, 0.0), axis=0,
                      keepdims=True)                   # (1, Q)
        col = csg[:, j * self.p:j * self.p + 1]        # (Q, 1)
        return jnp.exp(jnp.where(self.tril, col - row, -jnp.inf))

    def state_decay(self, csg):
        """exp of each head's whole-chunk log-decay, over the group's state
        rows (128, 1)."""
        q, p = self.q, self.p
        row = lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
        out = jnp.broadcast_to(csg[q - 1:q, 0:1], row.shape)
        for j in range(1, self.k):
            out = jnp.where(row >= j * p, csg[q - 1:q, j * p:j * p + 1], out)
        return jnp.exp(out)


def _fwd_kernel(x_ref, dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, h0_ref,
                y_ref, hT_ref, st_ref, h_scr, dtg_scr, csg_scr, *, p: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    k = _Chunk(dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, dtg_scr,
               csg_scr, p)

    def group(g, carry):
        cols, heads, dtg, csg = k.group(g)
        xs = x_ref[0, :, cols].astype(_F32)            # (Q, 128)
        xdt = xs * dtg
        y_diag = _by_head([_dot(k.scores * k.decay(csg, j, h), xdt)
                           for j, h in enumerate(heads)], p)
        hp = h_scr[cols, :]                            # (128, N) entering
        y_off = _dot(k.cm, hp, _NT) * jnp.exp(csg)
        y_ref[0, :, cols] = (y_diag + y_off).astype(y_ref.dtype)
        st_ref[0, 0, cols, :] = hp
        coef = jnp.exp(csg[k.q - 1:k.q, :] - csg) * dtg
        h_scr[cols, :] = hp * k.state_decay(csg) + _dot(xs * coef, k.bm, _TN)
        return carry

    lax.fori_loop(0, k.nh // k.k, group, 0)

    @pl.when(ic == pl.num_programs(1) - 1)
    def _final():
        hT_ref[0] = h_scr[...]


def _bwd_kernel(x_ref, dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, st_ref,
                dy_ref, dhT_ref, dx_ref, ddt_ref, dda_ref, ddat_ref, db_ref,
                dc_ref, dh0_ref, dh_scr, dtg_scr, csg_scr, *, p: int):
    """One chunk of the reverse sweep.  ``dh_scr`` holds the gradient of
    the state leaving this chunk and leaves with that of the state
    entering it.  Per position it returns d(dt) through dt's direct uses
    and d(dt * a), the gradient of the log-decay, in two parts: one in
    columns (Q, H) and, from the decay matrices' column sums, one in rows
    (H, Q)."""
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        dh_scr[...] = dhT_ref[0]

    k = _Chunk(dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, dtg_scr,
               csg_scr, p)
    q, nh = k.q, k.nh
    head_col = _lane((q, nh))
    lane = _lane((q, LANES))

    def group(g, acc):
        ds, db, dc, dcs, dcs_t, dcoef, ddt, dtot = acc
        cols, heads, dtg, csg = k.group(g)
        xs = x_ref[0, :, cols].astype(_F32)
        dys = dy_ref[0, :, cols].astype(_F32)
        xdt = xs * dtg
        dxdt = []
        for j, h in enumerate(heads):
            lmat = k.decay(csg, j, h)
            m = k.scores * lmat
            dxdt.append(_dot(m, dys, _TN))            # m^T @ dy
            own = (lane >= j * p) & (lane < (j + 1) * p)
            dm = _dot(jnp.where(own, dys, 0.0), xdt, _NT)
            ds = ds + dm * lmat
            # L[i, j] = exp(cs_i - cs_j): d cs from the row and column
            # sums of one product, so that their large parts cancel
            gm = dm * m
            dcs = jnp.where(head_col == h,
                            dcs + jnp.sum(gm, axis=1, keepdims=True), dcs)
            dcs_t = jnp.where(k.head_row == h,
                              dcs_t - jnp.sum(gm, axis=0, keepdims=True),
                              dcs_t)
        dxdt = _by_head(dxdt, p)
        hp = st_ref[0, 0, cols, :]                     # (128, N) entering
        dhn = dh_scr[cols, :]                          # d state leaving
        e = jnp.exp(csg)
        dye = dys * e
        dc = dc + _dot(dye, hp)
        dcs = _head_sums(dye * _dot(k.cm, hp, _NT), p, heads, dcs)
        coef = jnp.exp(csg[q - 1:q, :] - csg) * dtg
        dxc = _dot(k.bm, dhn, _NT)                     # d (x * coef)
        dx_ref[0, :, cols] = (dxc * coef + dxdt * dtg).astype(dx_ref.dtype)
        db = db + _dot(xs * coef, dhn)
        dcoef = _head_sums(dxc * xs, p, heads, dcoef)
        ddt = _head_sums(dxdt * xs, p, heads, ddt)
        decay = k.state_decay(csg)                     # (128, 1)
        carried = jnp.sum(dhn * hp, axis=1, keepdims=True) * decay
        row = lax.broadcasted_iota(jnp.int32, carried.shape, 0)
        col = _lane(dtot.shape)
        for j, h in enumerate(heads):
            own = (row >= j * p) & (row < (j + 1) * p)
            s = jnp.sum(jnp.where(own, carried, 0.0), axis=0, keepdims=True)
            dtot = jnp.where(col == h, dtot + s, dtot)
        dh_scr[cols, :] = dhn * decay + _dot(dye, k.cm, _TN)
        return ds, db, dc, dcs, dcs_t, dcoef, ddt, dtot

    zeros = lambda *shape: jnp.zeros(shape, _F32)
    ds, db, dc, dcs, dcs_t, dcoef, ddt, dtot = lax.fori_loop(
        0, nh // k.k, group,
        (zeros(q, q),                  # d(C.B^T), all heads
         zeros(*k.bm.shape), zeros(*k.cm.shape),
         zeros(q, nh),                 # d cs (columns), but the coef term
         zeros(nh, q),                 # d cs (rows): the decays' column sums
         zeros(q, nh),                 # d coef, coef = dt * exp(cs_{Q-1} - cs)
         zeros(q, nh),                 # d dt through x * dt
         zeros(1, nh)))                # d cs_{Q-1} through the state decay
    db_ref[0] = (db + _dot(ds, k.cm, _TN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(ds, k.bm)).astype(dc_ref.dtype)
    coef_e = jnp.exp(k.cs[q - 1:q, :] - k.cs)         # (Q, H) decay to end
    dcoef_c = dcoef * k.dt * coef_e
    ddt_ref[0] = ddt + dcoef * coef_e
    dtot = dtot + jnp.sum(dcoef_c, axis=0, keepdims=True)
    dda_ref[0] = _prefix_sum(dcs - dcoef_c, 0, reverse=True) + dtot
    ddat_ref[0] = _prefix_sum(dcs_t, 1, reverse=True)

    @pl.when(ic == pl.num_programs(1) - 1)
    def _final():
        dh0_ref[0] = dh_scr[...]


def _block_bytes(q: int, nh: int, p: int, n: int, x_bytes: int) -> int:
    """VMEM of one program's double-buffered blocks and scratch, plus the
    (Q, Q) float32 temporaries of a few heads in flight."""
    hp = nh * p
    blocks = (3 * q * hp * max(x_bytes, 4)     # x, dy, dx (y)
              + 3 * hp * n * 4                 # states in, carried, out
              + 2 * q * n * 4 * 2              # b, c and their gradients
              + 4 * q * LANES * 4)             # dt, its transpose, d dt
    return 2 * blocks + hp * n * 4 + 2 * q * hp * 4 + 12 * q * q * 4


def _compiler_params(q, nh, p, n, x_bytes):
    limit = max(32 * 2 ** 20, int(1.25 * _block_bytes(q, nh, p, n, x_bytes)))
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=limit)


def _specs(q, nh, p, n, nc, reverse):
    """Block specs of the inputs both kernels share."""
    c = (lambda ic: nc - 1 - ic) if reverse else (lambda ic: ic)
    hp = nh * p
    return [
        pl.BlockSpec((1, q, hp), lambda ib, ic: (ib, c(ic), 0)),      # x
        pl.BlockSpec((1, q, nh), lambda ib, ic: (ib, c(ic), 0)),      # dt
        pl.BlockSpec((1, nh, q), lambda ib, ic: (ib, 0, c(ic))),      # dt^T
        pl.BlockSpec((1, nh), lambda ib, ic: (0, 0)),                 # a row
        pl.BlockSpec((nh, 1), lambda ib, ic: (0, 0)),                 # a col
        pl.BlockSpec((1, q, n), lambda ib, ic: (ib, c(ic), 0)),       # b
        pl.BlockSpec((1, q, n), lambda ib, ic: (ib, c(ic), 0)),       # c
    ]


def _decay_args(dt, a):
    a = a.astype(_F32)
    return (dt, jnp.swapaxes(dt, 1, 2), a.reshape(1, -1), a.reshape(-1, 1))


@functools.partial(jax.jit, static_argnames=("chunk", "p", "out_dtype"))
def ssd_forward(x, dt, a, b, c, h0, *, chunk: int, p: int, out_dtype):
    """x: (B, L, H*P); dt: (B, L, H) f32; a: (H,); b, c: (B, L, N);
    h0: (B, H*P, N) f32.  Returns y (B, L, H*P) ``out_dtype``, the final
    state (B, H*P, N) and the state entering each chunk (B, L/Q, H*P, N),
    both f32."""
    bsz, l, hp = x.shape
    nh, n, q = dt.shape[-1], b.shape[-1], chunk
    nc = l // q
    state = pl.BlockSpec((1, hp, n), lambda ib, ic: (ib, 0, 0))
    return pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(bsz, nc),
        in_specs=_specs(q, nh, p, n, nc, False) + [state],
        out_specs=[
            pl.BlockSpec((1, q, hp), lambda ib, ic: (ib, ic, 0)),
            state,
            pl.BlockSpec((1, 1, hp, n), lambda ib, ic: (ib, ic, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, l, hp), out_dtype),
                   jax.ShapeDtypeStruct((bsz, hp, n), _F32),
                   jax.ShapeDtypeStruct((bsz, nc, hp, n), _F32)],
        scratch_shapes=[pltpu.VMEM((hp, n), _F32), pltpu.VMEM((q, hp), _F32),
                        pltpu.VMEM((q, hp), _F32)],
        compiler_params=_compiler_params(q, nh, p, n, x.dtype.itemsize),
    )(x, *_decay_args(dt, a), b, c, h0)


@functools.partial(jax.jit, static_argnames=("chunk", "p"))
def ssd_backward(x, dt, a, b, c, states, dy, dh_final, *, chunk: int,
                 p: int):
    """The reverse sweep over the chunks.  Returns dx (B, L, H*P) f32,
    d(dt) through dt's direct uses and d(dt * a) (both (B, L, H) f32), a
    second part of d(dt * a) in (B, H, L), dB and dC (B, L, N) f32, summed
    over heads, and d(h0) (B, H*P, N)."""
    bsz, l, hp = x.shape
    nh, n, q = dt.shape[-1], b.shape[-1], chunk
    nc = l // q
    rev = lambda ic: nc - 1 - ic
    state = pl.BlockSpec((1, hp, n), lambda ib, ic: (ib, 0, 0))
    per_pos = lambda w: pl.BlockSpec((1, q, w), lambda ib, ic: (ib, rev(ic),
                                                                 0))
    return pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(bsz, nc),
        in_specs=_specs(q, nh, p, n, nc, True) + [
            pl.BlockSpec((1, 1, hp, n), lambda ib, ic: (ib, rev(ic), 0, 0)),
            per_pos(hp), state],
        out_specs=[per_pos(hp), per_pos(nh), per_pos(nh),
                   pl.BlockSpec((1, nh, q), lambda ib, ic: (ib, 0, rev(ic))),
                   per_pos(n), per_pos(n), state],
        out_shape=[jax.ShapeDtypeStruct((bsz, l, hp), _F32),
                   jax.ShapeDtypeStruct((bsz, l, nh), _F32),
                   jax.ShapeDtypeStruct((bsz, l, nh), _F32),
                   jax.ShapeDtypeStruct((bsz, nh, l), _F32),
                   jax.ShapeDtypeStruct((bsz, l, n), _F32),
                   jax.ShapeDtypeStruct((bsz, l, n), _F32),
                   jax.ShapeDtypeStruct((bsz, hp, n), _F32)],
        scratch_shapes=[pltpu.VMEM((hp, n), _F32), pltpu.VMEM((q, hp), _F32),
                        pltpu.VMEM((q, hp), _F32)],
        compiler_params=_compiler_params(q, nh, p, n, x.dtype.itemsize),
    )(x, *_decay_args(dt, a), b, c, states, dy, dh_final)
