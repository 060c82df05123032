"""Tiled Pallas Winograd F(m, 3) convolution — one kernel, VMEM-resident
Winograd domain.

The pure-jnp baseline (core/winograd.py) materializes every Winograd-
domain tensor through HBM: the transformed input V ((m+2)^2/m^2 times
the input size — 4x for F(2,3)), the per-position products, and the
untransformed output tiles.  This kernel keeps the whole domain in
VMEM: each grid step stages a block of ``tt`` input tiles, runs the
B^T d B transform in-register (the transform matrices are tiny sparse
constants — unrolled scalar-multiply/adds on the VPU, no MXU), feeds
the (m+2)^2 per-position ``(tt x tc) @ (tc x tm)`` channel GEMMs into
an fp32 VMEM accumulator across contraction steps, and on the final
channel step applies the A^T m A inverse transform plus the fused
bias / residual-add / ReLU epilogue before the single HBM write.

Grid: ``(tiles/tt, M/tm, C/tc)`` with the contraction innermost
("arbitrary") so the accumulator survives revisits — the same layout
discipline as conv1x1.py.  Tile tensors are laid out position-major
``((m+2)^2, tiles, C)`` so each per-position GEMM is a plain 2-D
``jnp.dot`` on the MXU.

Tuning dims (the winograd_pallas executor's launch-config space):
``m`` (F(m,3) variant, 2 or 4), ``tt`` (tiles per block), ``tm``
(output-channel tile), ``tc`` (input-channel tile).

The filter transform U = G g G^T is computed once outside the kernel
(it is (m+2)^2 x C x M — small, reused by every tile block) at f32;
the in-kernel domain math is f32 regardless of operand dtype, so bf16
inputs keep fp32 Winograd accuracy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.winograd import matrices, transform_filters
from repro.kernels import _compat


def _lincomb(mat, rows):
    """``out[i] = sum_j mat[i, j] * rows[j]`` with zero entries skipped —
    the transform matrices are sparse small constants, so the transforms
    are a handful of VPU scalar-multiply/adds, never an MXU matmul."""
    out = []
    for i in range(mat.shape[0]):
        acc = None
        for j in range(mat.shape[1]):
            coef = float(mat[i, j])
            if coef == 0.0:
                continue
            term = rows[j] if coef == 1.0 else rows[j] * coef
            acc = term if acc is None else acc + term
        out.append(acc)
    return out


def _make_kernel(m, has_bias, has_add, activation):
    a = m + 2
    R = a * a
    BT, _, AT = matrices(m)

    def kernel(*refs):
        refs = list(refs)
        d_ref, u_ref = refs[0], refs[1]
        pos = 2
        b_ref = refs[pos] if has_bias else None
        pos += 1 if has_bias else 0
        ad_ref = refs[pos] if has_add else None
        pos += 1 if has_add else 0
        o_ref, acc_ref = refs[pos], refs[pos + 1]

        c = pl.program_id(2)
        d = d_ref[...].astype(jnp.float32)          # (R, tt, tc)
        # B^T d B over the two a-length tile axes (unrolled, sparse)
        t1 = [[None] * a for _ in range(a)]          # t1[i][k]
        for k in range(a):
            col = _lincomb(BT, [d[j * a + k] for j in range(a)])
            for i in range(a):
                t1[i][k] = col[i]
        V = [None] * R                               # V[i*a+l] = (tt, tc)
        for i in range(a):
            row = _lincomb(BT, t1[i])
            for l in range(a):
                V[i * a + l] = row[l]

        # per-position channel GEMMs, fp32-accumulated across C steps
        u = u_ref[...]                               # (R, tc, tm) f32
        part = jnp.stack([jnp.dot(V[r], u[r],
                                  preferred_element_type=jnp.float32)
                          for r in range(R)])        # (R, tt, tm)

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(c > 0)
        def _accumulate():
            acc_ref[...] += part

        @pl.when(c == pl.num_programs(2) - 1)
        def _finish():
            acc = acc_ref[...]
            mg = [[acc[i * a + l] for l in range(a)] for i in range(a)]
            # inverse transform A^T m A, then the fused epilogue
            t2 = [[None] * a for _ in range(m)]      # t2[u][l]
            for l in range(a):
                col = _lincomb(AT, [mg[i][l] for i in range(a)])
                for u_ in range(m):
                    t2[u_][l] = col[u_]
            ys = []
            for u_ in range(m):
                ys.extend(_lincomb(AT, t2[u_]))
            y = jnp.stack(ys)                        # (m*m, tt, tm)
            if has_bias:
                y = y + b_ref[...].astype(jnp.float32)[0]
            if has_add:
                y = y + ad_ref[...].astype(jnp.float32)
            if activation == "relu":
                y = jnp.maximum(y, 0.0)
            o_ref[...] = y.astype(o_ref.dtype)

    return kernel


def vmem_bytes(in_shape, filter_shape, m=2, tt=128, tm=128, tc=128,
               itemsize=4, bias=False, addend=False):
    """Live-block VMEM model of one grid step at the TPU's tiled layout
    (``_compat.tiled_bytes``): input-tile and transformed-filter blocks
    double buffered, the f32 Winograd-domain accumulator, the output-tile
    block double buffered, the epilogue operands, and the in-kernel
    values a step holds (the transformed input tiles and the
    per-position GEMM products).  Against the compiler's own scoped
    allocation for one F(4,3) 128-wide-tile step (17.0 MiB), this model
    gives 17.8 MiB."""
    a = m + 2
    R = a * a
    tb = _compat.tiled_bytes
    need = (2 * (tb((R, tt, tc), itemsize) + tb((R, tc, tm), 4))
            + tb((R, tt, tm), 4)                            # f32 domain acc
            + 2 * tb((m * m, tt, tm), itemsize)             # output tiles
            + tb((R, tt, tc), 4)                            # B^T d B values
            + tb((R, tt, tm), 4))                           # GEMM products
    if bias:
        need += 2 * tb((1, tm), 4)
    if addend:
        need += 2 * tb((m * m, tt, tm), itemsize)
    return int(need)


@functools.partial(jax.jit, static_argnames=(
    "padding", "activation", "m", "tt", "tm", "tc", "interpret"))
def winograd_fused(x, w, padding=(1, 1), bias=None, activation=None,
                   addend=None, m=2, tt=128, tm=128, tc=128, *,
                   interpret):
    """x: (N, H, W, C) NHWC; w: (3, 3, C, M); stride-1 only.

    ``bias`` (M,), ``activation`` (None | 'relu') and ``addend``
    (residual second operand, output-shaped) are fused into the kernel
    epilogue — applied in VMEM after the inverse transform, before the
    single HBM write.  ``interpret`` is required: callers resolve it
    per backend (``kernels.ops``).  Returns (N, OH, OW, M) in ``x.dtype``.
    """
    N, H, W_, C = x.shape
    M = w.shape[3]
    ph, pw = padding
    OH, OW = H + 2 * ph - 2, W_ + 2 * pw - 2
    a = m + 2
    R = a * a
    th, tw = -(-OH // m), -(-OW // m)
    Hp, Wp = m * th + 2, m * tw + 2
    xp = jnp.pad(x, ((0, 0), (ph, Hp - H - ph), (pw, Wp - W_ - pw), (0, 0)))

    # overlapping a x a tiles with stride m, position-major (R, P, C)
    i_idx = (m * jnp.arange(th))[:, None] + jnp.arange(a)[None, :]
    j_idx = (m * jnp.arange(tw))[:, None] + jnp.arange(a)[None, :]
    tiles = xp[:, i_idx][:, :, :, j_idx]          # (N, th, a, tw, a, C)
    tiles = tiles.transpose(2, 4, 0, 1, 3, 5)     # (a, a, N, th, tw, C)
    P = N * th * tw
    d = tiles.reshape(R, P, C)
    U = transform_filters(w.astype(jnp.float32), m).reshape(R, C, M)

    (tt, tm, tc), (pp, pm, pc) = _compat.clamp_tiles((P, M, C),
                                                     (tt, tm, tc))
    d = jnp.pad(d, ((0, 0), (0, pp), (0, pc)))
    U = jnp.pad(U, ((0, 0), (0, pc), (0, pm)))
    grid = ((P + pp) // tt, (M + pm) // tm, (C + pc) // tc)

    has_bias = bias is not None
    has_add = addend is not None
    in_specs = [
        pl.BlockSpec((R, tt, tc), lambda p, mo, c: (0, p, c)),
        pl.BlockSpec((R, tc, tm), lambda p, mo, c: (0, c, mo)),
    ]
    operands = [d, U]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, tm), lambda p, mo, c: (0, mo)))
        operands.append(jnp.pad(bias.reshape(1, M), ((0, 0), (0, pm))))
    if has_add:
        # gather the residual operand into the same output-tile layout
        ad = jnp.pad(addend, ((0, 0), (0, m * th - OH), (0, m * tw - OW),
                              (0, 0)))
        ad = ad.reshape(N, th, m, tw, m, M).transpose(2, 4, 0, 1, 3, 5)
        ad = jnp.pad(ad.reshape(m * m, P, M), ((0, 0), (0, pp), (0, pm)))
        in_specs.append(pl.BlockSpec((m * m, tt, tm),
                                     lambda p, mo, c: (0, p, mo)))
        operands.append(ad)
    out = pl.pallas_call(
        _make_kernel(m, has_bias, has_add, activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m * m, tt, tm), lambda p, mo, c: (0, p, mo)),
        out_shape=jax.ShapeDtypeStruct((m * m, P + pp, M + pm), x.dtype),
        scratch_shapes=[pltpu.VMEM((R, tt, tm), jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"winograd_f{m}_fused",
    )(*operands)
    y = out[:, :P, :M].reshape(m, m, N, th, tw, M)
    y = y.transpose(2, 3, 0, 4, 1, 5).reshape(N, m * th, m * tw, M)
    return y[:, :OH, :OW, :]
