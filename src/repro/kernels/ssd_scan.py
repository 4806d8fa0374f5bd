"""Pallas TPU SSD (state-space duality) scan — Mamba2's compute core,
forward and backward, differentiable through ``jax.custom_vjp``.

Per chunk of q timesteps and head (cs = chunk-local cumsum of the
log-decay ``a``, T = cs at the chunk's end, h0 the state at its start):
    L[t,j]  = exp(cs[t] - cs[j]) for j<=t, else 0         (decay matrix)
    y       = ((C B^T) * L) x + diag(exp(cs)) C h0^T
    h1      = exp(T) h0 + sum_j exp(T - cs[j]) x_j^T B_j

Forward grid: (batch, chunks), the chunk axis sequential ("arbitrary").
Each step reads the chunk of every head at once, x and y in the model's
[b, s, h*p] layout, and unrolls a loop over blocks of heads that fill 128
lanes.  The state of every head lives in one f32 VMEM scratch, transposed
to [n, h*p], so ``C h0^T`` and the state update are one untransposed
product per block; the head-shared ``C B^T`` is formed once per chunk, and
the heads of a block share one product with x by stacking their [q, q]
matrices.  Under differentiation the forward also writes each chunk's
starting state, the backward's one residual.

Backward grid: (batch, chunks in reverse).  The state's gradient dh is
carried in f32 VMEM scratch the same way; each step gives dx, the
chunk's dB and dC summed over heads inside the kernel, and the gradient
of cs, whose rows and columns the kernels read in two layouts.  The
chunk-local cumsum, and everything upstream of ``a``, stays in jnp
autodiff outside the op.

Precision: no less than ``models.mamba2.ssd_chunked``'s on the TPU.
Decays, cumsums, the state, dh and every accumulation stay f32.  Products
run on the matrix unit with f32 accumulation: at HIGHEST for f32 inputs;
for bf16 inputs, operands that are inputs (x, B, C, dy) enter as they are
and operands the kernel computed in f32 enter as hi + lo bf16 parts (see
``_dot``), so the gradients miss the f32 ones by no more than the jnp
scan's bf16 gradients do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NN = (((1,), (0,)), ((), ()))       # a @ b
NT = (((1,), (1,)), ((), ()))       # a @ b^T
TN = (((0,), (0,)), ((), ()))       # a^T @ b
# At chunk 256 the backward's blocks and temporaries take about 16 MiB of
# VMEM, the compiler's default limit, which one train step's layout passed
# by 116 KiB; v5e has 128 MiB.
_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                               vmem_limit_bytes=32 * 2**20)


def _dot(lhs, rhs, dims, dtype):
    """Matrix-unit product, accumulated in f32.  f32 inputs run at HIGHEST:
    Mosaic's default contracts f32 in a single bf16 pass, which misses the
    f32 reference by percents.  For bf16 inputs, an operand the kernel
    computed in f32 (S, the state, dh, a decayed x or dy) is split into hi
    and lo bf16 parts and the parts' products summed, lo x lo left out.
    In one bf16 pass such operands made the bf16 gradients miss the f32
    ones by 1.4 times what the jnp scan's miss them by, on the chip."""
    if dtype == F32:
        return jax.lax.dot_general(lhs, rhs, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=F32)

    def parts(v):
        if v.dtype == dtype:
            return [v]
        hi = v.astype(dtype)
        return [hi, (v - hi.astype(F32)).astype(dtype)]

    out = None
    for i, u in enumerate(parts(lhs)):
        for j, w in enumerate(parts(rhs)):
            if i + j < 2:
                t = jax.lax.dot_general(u, w, dims, preferred_element_type=F32)
                out = t if out is None else out + t
    return out


def _heads_per_block(h: int, p: int) -> int:
    """The fewest heads whose p-wide slices fill whole 128-lane tiles."""
    for hb in range(1, h + 1):
        if h % hb == 0 and hb * p % 128 == 0:
            return hb
    return h


class _Chunk:
    """What every head block of one grid step shares: the chunk's cs in
    both orientations, its causal mask, and lane masks of a head block."""

    def __init__(self, cs_ref, cst_ref, *, q, p, hb):
        self.cs = cs_ref[0, 0]                          # [h, q]
        self.cst = cst_ref[0, 0]                        # [q, h]
        self.p, self.hb = p, hb
        ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        self.causal, self.anticausal = jj <= ii, jj >= ii
        self.last = ii[:, :1] == q - 1                  # [q, 1]
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (1, hb * p), 1)

    def decay(self, row, col):
        """L[t, j] = exp(cs[t] - cs[j]) for j <= t, else 0."""
        return jnp.where(self.causal, jnp.exp(col - row), 0.0)

    def decay_t(self, row, col):
        """L^T, built as such: on the chip this beats transposing L."""
        return jnp.where(self.anticausal, jnp.exp(row - col), 0.0)

    def head(self, hd):
        """Head ``hd``'s cs as a row [1,q] and a column [q,1], and its
        value at the chunk's end T [1,1]."""
        col = self.cst[:, hd:hd + 1]
        return self.cs[hd:hd + 1, :], col, col[-1:, :]

    def on(self, k):
        """[1, hb*p]: the lanes of the block's head k."""
        return (self.lane >= k * self.p) & (self.lane < (k + 1) * self.p)

    def per_lane(self, heads, f):
        """f(head k's row, col, T) on head k's lanes of a block."""
        out = f(*heads[0])
        for k in range(1, self.hb):
            out = jnp.where(self.on(k), f(*heads[k]), out)
        return out

    def rows_to_lanes(self, stacked, q):
        """Rows k*q:(k+1)*q of ``stacked`` on head k's lanes."""
        out = stacked[:q]
        for k in range(1, self.hb):
            out = jnp.where(self.on(k), stacked[k * q:(k + 1) * q], out)
        return out


def _fwd_kernel(x_ref, cs_ref, cst_ref, b_ref, c_ref, y_ref, hlast_ref,
                *refs, q, p, hb, nblk, residuals):
    ht_ref = refs[-1]                                   # state^T, [n, h*p]
    dt = x_ref.dtype
    w = hb * p

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ht_ref[...] = jnp.zeros_like(ht_ref)

    B = b_ref[0]                                        # [q, n]
    C = c_ref[0]
    CB = _dot(C, B, NT, dt)                             # [q, q], all heads
    Bt = B.T                                            # [n, q]
    ck = _Chunk(cs_ref, cst_ref, q=q, p=p, hb=hb)
    for g in range(nblk):
        cols = slice(g * w, (g + 1) * w)
        x = x_ref[0, :, cols]                           # [q, w]
        h0 = ht_ref[:, cols]                            # [n, w] f32
        if residuals:
            refs[0][0, 0, :, cols] = h0
        heads = [ck.head(g * hb + k) for k in range(hb)]
        S = jnp.concatenate([CB * ck.decay(r, c) for r, c, _ in heads],
                            axis=0)
        y = (ck.rows_to_lanes(_dot(S, x, NN, dt), q)
             + ck.per_lane(heads, lambda r, c, t: jnp.exp(c))
             * _dot(C, h0, NN, dt))
        y_ref[0, :, cols] = y.astype(y_ref.dtype)
        to_end = ck.per_lane(heads, lambda r, c, t: jnp.exp(t - c))
        ht_ref[:, cols] = (ck.per_lane(heads, lambda r, c, t: jnp.exp(t)) * h0
                           + _dot(Bt, x * to_end, NN, dt))

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _fin():
        hlast_ref[0] = ht_ref[...]


def _bwd_kernel(x_ref, cs_ref, cst_ref, b_ref, c_ref, h0_ref, dy_ref,
                dhlast_ref, dx_ref, dcs_ref, dcst_ref, db_ref, dc_ref,
                dht_ref, *, q, p, hb, nblk):
    dt = x_ref.dtype
    w = hb * p

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dht_ref[...] = dhlast_ref[0]

    B = b_ref[0]
    C = c_ref[0]
    CB = _dot(C, B, NT, dt)
    BC = _dot(B, C, NT, dt)                             # CB^T
    Ct = C.T                                            # [n, q]
    ck = _Chunk(cs_ref, cst_ref, q=q, p=p, hb=hb)
    h = nblk * hb
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, h), 1)
    dcb = jnp.zeros((q, q), F32)
    dc = jnp.zeros(C.shape, F32)
    db = jnp.zeros(B.shape, F32)
    drows, dcols = [], jnp.zeros((q, h), F32)
    for g in range(nblk):
        cols = slice(g * w, (g + 1) * w)
        x = x_ref[0, :, cols]                           # [q, w]
        dy = dy_ref[0, :, cols]
        h0 = h0_ref[0, 0, :, cols]                      # [n, w] f32
        dh1 = dht_ref[:, cols]                          # d(state at chunk end)
        heads = [ck.head(g * hb + k) for k in range(hb)]
        from_start = ck.per_lane(heads, lambda r, c, t: jnp.exp(c))
        to_end = ck.per_lane(heads, lambda r, c, t: jnp.exp(t - c))
        # inter-chunk term: y_inter = from_start * (C h0^T)
        dyd = dy.astype(F32) * from_start               # [q, w]
        dc = dc + _dot(dyd, h0, NT, dt)
        ydy = dyd * _dot(C, h0, NN, dt)                 # dy * y_inter
        # state update: h1 = exp(T) h0 + (x * to_end)^T B
        bdh = _dot(B, dh1, NN, dt)                      # B dh1^T, [q, w]
        db = db + _dot(x * to_end, dh1, NT, dt)
        xb = x * bdh
        hh = jnp.sum(h0 * dh1, axis=0, keepdims=True)   # [1, w]
        dht_ref[:, cols] = (
            ck.per_lane(heads, lambda r, c, t: jnp.exp(t)) * dh1
            + _dot(Ct, dyd, NN, dt))
        # intra-chunk term: y_intra = S x, S = CB * L, per head
        Ls = [ck.decay(r, c) for r, c, _ in heads]
        St = jnp.concatenate([BC * ck.decay_t(r, c) for r, c, _ in heads],
                             axis=0)
        dx = bdh * to_end + ck.rows_to_lanes(_dot(St, dy, NN, dt), q)
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        dys = jnp.concatenate([jnp.where(ck.on(k), dy, 0)
                               for k in range(hb)], axis=0)
        dS = _dot(dys, x, NT, dt)                       # dy_k x_k^T, stacked
        for k, (r, c, t) in enumerate(heads):
            dSk = dS[k * q:(k + 1) * q]
            dcb = dcb + dSk * Ls[k]
            G = dSk * CB * Ls[k]                        # d(cs_t - cs_j) terms
            dw = (jnp.sum(jnp.where(ck.on(k), xb, 0.0), axis=1, keepdims=True)
                  * jnp.exp(t - c))
            dT = (jnp.exp(t) * jnp.sum(jnp.where(ck.on(k), hh, 0.0), axis=1,
                                       keepdims=True)
                  + jnp.sum(dw, axis=0, keepdims=True))
            dcol = (jnp.sum(G, axis=1, keepdims=True)
                    + jnp.sum(jnp.where(ck.on(k), ydy, 0.0), axis=1,
                              keepdims=True)
                    - dw + jnp.where(ck.last, dT, 0.0))
            dcols = jnp.where(head_lane == g * hb + k, dcol, dcols)
            drows.append(-jnp.sum(G, axis=0, keepdims=True))
    dcs_ref[0, 0] = jnp.concatenate(drows, axis=0)
    dcst_ref[0, 0] = dcols
    dc_ref[0] = (dc + _dot(dcb, B, NN, dt)).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dcb, C, TN, dt)).astype(db_ref.dtype)


def _geometry(x, cs, B):
    b, s, hp = x.shape
    _, nc, h, q = cs.shape
    p, n = hp // h, B.shape[-1]
    hb = _heads_per_block(h, p)
    return b, s, h, p, n, q, nc, hb


def _forward(x, cs, cst, B, C, *, residuals, interpret):
    b, s, h, p, n, q, nc, hb = _geometry(x, cs, B)
    hp = h * p
    out_specs = [pl.BlockSpec((1, q, hp), lambda i, c: (i, c, 0)),
                 pl.BlockSpec((1, n, hp), lambda i, c: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, s, hp), x.dtype),
                 jax.ShapeDtypeStruct((b, n, hp), F32)]
    if residuals:
        out_specs.append(pl.BlockSpec((1, 1, n, hp),
                                      lambda i, c: (i, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, nc, n, hp), F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, q=q, p=p, hb=hb, nblk=h // hb,
                          residuals=residuals),
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, q, hp), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, h, q), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, h), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, q, n), lambda i, c: (i, c, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, hp), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_fwd",
    )(x, cs, cst, B, C)


def _backward(x, cs, cst, B, C, h0s, dy, dhlast, *, interpret):
    b, s, h, p, n, q, nc, hb = _geometry(x, cs, B)
    hp = h * p

    def rev(block):       # chunk nc-1 first
        return pl.BlockSpec(
            block, lambda i, c: (i, nc - 1 - c) + (0,) * (len(block) - 2))

    seq, bc = (1, q, hp), (1, q, n)
    cs_blk, cst_blk, state = (1, 1, h, q), (1, 1, q, h), (1, 1, n, hp)
    dx, dcs, dcst, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, q=q, p=p, hb=hb, nblk=h // hb),
        grid=(b, nc),
        in_specs=[rev(seq), rev(cs_blk), rev(cst_blk), rev(bc), rev(bc),
                  rev(state), rev(seq),
                  pl.BlockSpec((1, n, hp), lambda i, c: (i, 0, 0))],
        out_specs=[rev(seq), rev(cs_blk), rev(cst_blk), rev(bc), rev(bc)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(cs.shape, F32),
            jax.ShapeDtypeStruct((b, nc, q, h), F32),
            jax.ShapeDtypeStruct(B.shape, B.dtype),
            jax.ShapeDtypeStruct(C.shape, C.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, hp), F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_bwd",
    )(x, cs, cst, B, C, h0s, dy, dhlast)
    return dx, dcs, dcst, dB, dC


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, cs, cst, B, C, interpret):
    y, hlast = _forward(x, cs, cst, B, C, residuals=False, interpret=interpret)
    return y, hlast


def _ssd_fwd(x, cs, cst, B, C, interpret):
    y, hlast, h0s = _forward(x, cs, cst, B, C, residuals=True,
                             interpret=interpret)
    return (y, hlast), (x, cs, cst, B, C, h0s)


def _ssd_bwd(interpret, res, cts):
    dy, dhlast = cts
    return _backward(*res, dy, dhlast, interpret=interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@jax.named_scope("ssd")
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, a, B, C, *, chunk: int = 64, interpret: bool = False):
    """SSD over full sequences, as ``models.mamba2.ssd_chunked``.

    x: [b,s,h,p], a: [b,s,h] (log-decay), B/C: [b,s,n] (one group).
    Returns (y [b,s,h,p], final state [b,h,p,n] f32).  Sequences that are
    not a multiple of ``chunk`` are padded with identity steps (x = 0,
    a = 0).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = -s % chunk
    if pad:
        x, a, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                      for t in (x, a, B, C))
    nc = (s + pad) // chunk
    # chunk-local cumsum of a, in both layouts the kernels read, as products
    # with a triangle of ones on the matrix unit (f32 at HIGHEST); XLA's
    # cumsum, a reduce-window, is slower, its reverse in the backward more so
    ac = a.astype(F32).reshape(b, nc, chunk, h)
    tri = jnp.tril(jnp.ones((chunk, chunk), F32))           # [t, j]: j <= t
    hi = jax.lax.Precision.HIGHEST
    cs = jnp.einsum("tj,bcjh->bcht", tri, ac, precision=hi)  # [b, c, h, q]
    cst = jnp.einsum("tj,bcjh->bcth", tri, ac, precision=hi)  # [b, c, q, h]
    y, hlast = _ssd(x.reshape(b, s + pad, h * p), cs, cst, B, C,
                    interpret)
    return (y.reshape(b, s + pad, h, p)[:, :s],
            hlast.reshape(b, n, h, p).transpose(0, 2, 3, 1))
