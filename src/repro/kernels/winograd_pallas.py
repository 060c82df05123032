"""Tiled Pallas Winograd F(m, 3) convolution — one kernel, VMEM-resident
Winograd domain.

The pure-jnp baseline (core/winograd.py) materializes every Winograd-
domain tensor through HBM: the transformed input V ((m+2)^2/m^2 times
the input size — 4x for F(2,3)), the per-position products, and the
untransformed output tiles.  This kernel keeps the whole domain in
VMEM, the input tiles included: each grid step reads a band of the
input, forms its ``rows x twp`` tiles, runs the B^T d B transform
in-register (the transform matrices are tiny sparse constants —
unrolled scalar-multiply/adds on the VPU, no MXU), feeds the (m+2)^2
per-position ``(rows*twp x tc) @ (tc x tm)`` channel GEMMs into an
fp32 VMEM accumulator across contraction steps, and on the final
channel step applies the A^T m A inverse transform plus the fused
bias / residual-add / ReLU epilogue before the single HBM write.

Input: the padded input is split into ``m x m`` stride phases
(``_compat.phase_split``, one XLA pass over 1x the input).  Tile
``(t, s)`` at position ``(i, k)`` reads padded pixel ``(m*t + i,
m*s + k)``: phase ``(i % m, k % m)`` at phase offset ``(t + i // m,
s + k // m)``, so each of the (m+2)^2 positions of a band's tiles is
one unit-stride window of one phase, sliced from the ref with static
offsets (no gather).  A step's block is the halo'd band of ``rows +
(m+1)//m`` phase rows at an element offset (``pl.Element``), as in
cuconv_fused.py; where ``rows`` reaches an image's ``th`` tile rows, a
step takes ``nb`` whole images instead (``rows // th`` of them, or the
largest divisor of N below that), so small images still fill the MXU.
The tile columns are padded to whole sublane tiles (``twp``): at
54x54 the unpadded ``(rows, tw, C) -> (rows*tw, C)`` window reshape
ran 2.8x slower on a v5e (F(2,3), batch 32, f32); the padded tiles
read zeros.

Output: the epilogue writes NHWC directly.  Output pixel ``(m*t + u,
m*s + v)`` of position ``(u, v)`` is a stride-``m`` store into the
``(nb, m*rows, OW, tm)`` output block, so no XLA pass follows the
kernel.
Mosaic's strided stores take 32-bit data on at most 128 lanes, so
``tm <= 128`` (a ``config_supports`` rule) and 16-bit inputs get an
f32 output that XLA casts.

Grid: ``(N/nb, th/rows, M/tm, C/tc)`` with the contraction innermost
("arbitrary") so the accumulator survives revisits.  Under more than
one output-channel tile the transformed input of each channel tile is
kept in VMEM from the first output-channel tile, so B^T d B runs once
per band.

Tuning dims (the winograd_pallas executor's launch-config space):
``m`` (F(m,3) variant, 2 or 4), ``rows`` (tile rows per step, of one
image or, from ``th`` up, of whole images), ``tm``
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


def _make_kernel(m, nb, rows, twp, ow, cache_v, has_bias, has_add,
                 activation):
    a = m + 2
    R = a * a
    T = nb * rows * twp                              # tiles per step
    BT, _, AT = matrices(m)

    def kernel(*refs):
        refs = list(refs)
        x_ref, u_ref = refs.pop(0), refs.pop(0)
        b_ref = refs.pop(0) if has_bias else None
        ad_ref = refs.pop(0) if has_add else None
        o_ref, acc_ref = refs.pop(0), refs.pop(0)
        v_ref = refs.pop(0) if cache_v else None
        mo, c = pl.program_id(2), pl.program_id(3)

        def transform():
            # tile position (i, k) of tile (t, s) is padded pixel
            # (m*t + i, m*s + k): phase (i % m, k % m) at phase offset
            # (t + i // m, s + k // m) -- a unit-stride window of the band
            d = [[x_ref[:, i % m, pl.ds(i // m, rows), k % m,
                        pl.ds(k // m, twp), :].astype(jnp.float32)
                  .reshape(T, x_ref.shape[-1])
                  for k in range(a)] for i in range(a)]   # (T, tc) each
            # B^T d B over the two a-length tile axes (unrolled, sparse)
            t1 = [[None] * a for _ in range(a)]          # t1[i][k]
            for k in range(a):
                col = _lincomb(BT, [d[j][k] for j in range(a)])
                for i in range(a):
                    t1[i][k] = col[i]
            V = []                                       # V[i*a+l]
            for i in range(a):
                V.extend(_lincomb(BT, t1[i]))
            return V

        if cache_v:
            # B^T d B once per (band, channel tile): the first
            # output-channel tile keeps it for the others
            @pl.when(mo == 0)
            def _keep():
                v_ref[c] = jnp.stack(transform())

            V = v_ref[c]                                 # (R, T, tc)
        else:
            V = transform()

        # per-position channel GEMMs, fp32-accumulated across C steps
        u = u_ref[...]                                   # (R, tc, tm) f32
        part = jnp.stack([jnp.dot(V[r], u[r],
                                  preferred_element_type=jnp.float32)
                          for r in range(R)])            # (R, T, tm)

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(c > 0)
        def _accumulate():
            acc_ref[...] += part

        @pl.when(c == pl.num_programs(3) - 1)
        def _finish():
            acc = acc_ref[...]
            mg = [[acc[i * a + l] for l in range(a)] for i in range(a)]
            # inverse transform A^T m A, then the fused epilogue
            t2 = [[None] * a for _ in range(m)]          # t2[u][l]
            for l in range(a):
                col = _lincomb(AT, [mg[i][l] for i in range(a)])
                for u_ in range(m):
                    t2[u_][l] = col[u_]
            for u_ in range(m):
                for v, y in enumerate(_lincomb(AT, t2[u_])):
                    # output pixel (m*t + u, m*s + v): a stride-m store
                    # of the real tile columns into the NHWC block
                    nv = -(-(ow - v) // m)
                    y = y.reshape(nb, rows, twp, y.shape[-1])[:, :, :nv]
                    win = (slice(None), pl.ds(u_, rows, stride=m),
                           pl.ds(v, nv, stride=m), slice(None))
                    if has_bias:
                        y = y + b_ref[...].astype(jnp.float32)[0]
                    if has_add:
                        y = y + ad_ref[win].astype(jnp.float32)
                    if activation == "relu":
                        y = jnp.maximum(y, 0.0)
                    o_ref[win] = y.astype(o_ref.dtype)

    return kernel


def tile_grid(H, W, padding, m, itemsize):
    """``(th, twp)``: Winograd tile rows, and tile columns padded to
    whole sublane tiles, so the in-kernel ``(rows, twp, C) ->
    (rows*twp, C)`` window reshape keeps the TPU layout."""
    ph, pw = padding
    th, tw = -(-(H + 2 * ph - 2) // m), -(-(W + 2 * pw - 2) // m)
    sub = 8 * max(1, 4 // itemsize)
    return th, -(-tw // sub) * sub


def geometry(N, H, W, padding, m, rows, itemsize):
    """``(OH, OW, nb, rows, RB, twp, Hq, Wq)`` for ``rows`` tile rows
    per step: output extents; images per step and tile rows of each
    (below ``th`` a band of one image; from ``th`` up whole images, as
    many as ``rows // th`` allows and ``N`` divides into); row bands;
    padded tile columns (``tile_grid``); and the phase-split input
    extents the last band reads."""
    OH, OW = H + 2 * padding[0] - 2, W + 2 * padding[1] - 2
    th, twp = tile_grid(H, W, padding, m, itemsize)
    rows = max(1, int(rows))
    if rows < th:
        nb = 1
    else:
        nb = max(d for d in range(1, min(N, rows // th) + 1) if N % d == 0)
        rows = th
    RB = -(-th // rows)
    a = m + 2
    # past the real tiles the phases are zeros: they feed only tiles
    # (rows and columns) whose outputs are sliced away
    Hq, Wq = _compat.phase_extents(H, W, a, a, (m, m), padding,
                                   RB * rows, twp)
    return OH, OW, nb, rows, RB, twp, Hq, Wq


def vmem_bytes(in_shape, filter_shape, m=2, rows=4, tm=128, tc=128,
               itemsize=4, bias=False, addend=False, padding=(1, 1)):
    """Live-block VMEM model of one grid step at the TPU's tiled layout
    (``_compat.tiled_bytes``): the halo'd input band of every phase and
    the transformed-filter block double buffered, the f32 Winograd-
    domain accumulator, the NHWC output block (and the residual block)
    double buffered, the kept transformed input under more than one
    output-channel tile, and the in-kernel values a step holds (the
    transformed input tiles and the per-position GEMM products)."""
    a = m + 2
    R = a * a
    tb = _compat.tiled_bytes
    N, H, W, C = in_shape
    M = filter_shape[3]
    _, OW, nb, rows, _, twp, _, Wq = geometry(N, H, W, padding, m, rows,
                                               itemsize)
    T = nb * rows * twp
    band = rows + (a - 1) // m
    out_block = nb * tb((m * rows, OW, tm), 4)
    need = (2 * (nb * m * m * band * tb((Wq, tc), itemsize)
                 + tb((R, tc, tm), 4))
            + tb((R, T, tm), 4)                             # f32 domain acc
            + 2 * out_block                                 # output block
            + tb((R, T, tc), 4)                             # B^T d B values
            + tb((R, T, tm), 4))                            # GEMM products
    if M > tm:
        need += -(-C // tc) * tb((R, T, tc), 4)             # kept B^T d B
    if bias:
        need += 2 * tb((1, tm), 4)
    if addend:
        need += 2 * out_block
    return int(need)


@functools.partial(jax.jit, static_argnames=(
    "padding", "activation", "m", "rows", "tm", "tc", "interpret"))
def winograd_fused(x, w, padding=(1, 1), bias=None, activation=None,
                   addend=None, m=2, rows=4, tm=128, tc=128, *,
                   interpret):
    """x: (N, H, W, C) NHWC; w: (3, 3, C, M); stride-1 only.

    ``bias`` (M,), ``activation`` (None | 'relu') and ``addend``
    (residual second operand, output-shaped) are fused into the kernel
    epilogue — applied in VMEM after the inverse transform, before the
    single HBM write.  ``rows`` (tile rows per grid step), ``tm`` (at
    most ``_compat.STRIDED_LANES`` once clamped to M) and ``tc`` are
    the launch config.  ``interpret`` is required: callers resolve it
    per backend (``kernels.ops``).  Returns (N, OH, OW, M) in
    ``x.dtype``.
    """
    N, H, W_, C = x.shape
    M = w.shape[3]
    a = m + 2
    R = a * a
    OH, OW, nb, rows, RB, twp, Hq, Wq = geometry(
        N, H, W_, padding, m, rows, jnp.dtype(x.dtype).itemsize)
    band = rows + (a - 1) // m
    T = nb * rows * twp
    (tm, tc), (pm, pc) = _compat.clamp_tiles((M, C), (tm, tc))
    if tm > _compat.STRIDED_LANES:
        raise ValueError(f"the NHWC output store takes tm <= "
                         f"{_compat.STRIDED_LANES}; got tm={tm} for M={M}")
    # strided stores take 32-bit data: 16-bit inputs get an f32 output
    out_dtype = x.dtype if jnp.dtype(x.dtype).itemsize == 4 else jnp.float32

    # the only XLA pass over the input: pad + stride-phase split (1x)
    xq = _compat.phase_split(x, (m, m), padding, Hq, Wq)
    xq = jnp.pad(xq, ((0, 0),) * 5 + ((0, pc),))
    U = transform_filters(w.astype(jnp.float32), m).reshape(R, C, M)
    U = jnp.pad(U, ((0, 0), (0, pc), (0, pm)))
    grid = (N // nb, RB, (M + pm) // tm, (C + pc) // tc)
    cache_v = grid[2] > 1
    if grid[3] == 1:
        c_off = lambda c: 0          # noqa: E731 -- a static offset
    else:
        c_off = lambda c: c * tc     # noqa: E731 -- 128-lane aligned

    has_bias = bias is not None
    has_add = addend is not None
    in_specs = [
        # the halo'd band of every phase of nb images at element offset
        # r*rows, one channel tile (Mosaic takes element indexing on all
        # dims or none)
        pl.BlockSpec((pl.Element(nb), pl.Element(m), pl.Element(band),
                      pl.Element(m), pl.Element(Wq), pl.Element(tc)),
                     lambda n, r, mo, c: (n * nb, 0, r * rows, 0, 0,
                                          c_off(c))),
        pl.BlockSpec((R, tc, tm), lambda n, r, mo, c: (0, c, mo)),
    ]
    operands = [xq, U]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, tm), lambda n, r, mo, c: (0, mo)))
        operands.append(jnp.pad(bias.reshape(1, M), ((0, 0), (0, pm))))
    # the NHWC output block of one (images, row band, channel tile);
    # the last band's and channel tile's blocks may overhang OH and M
    out_spec = pl.BlockSpec((nb, m * rows, OW, tm),
                            lambda n, r, mo, c: (n, r, 0, mo))
    if has_add:
        in_specs.append(out_spec)
        operands.append(addend.astype(out_dtype))
    scratch = [pltpu.VMEM((R, T, tm), jnp.float32)]
    if cache_v:
        scratch.append(pltpu.VMEM((grid[3], R, T, tc), jnp.float32))
    out = pl.pallas_call(
        _make_kernel(m, nb, rows, twp, OW, cache_v, has_bias, has_add,
                     activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, M), out_dtype),
        scratch_shapes=scratch,
        compiler_params=_compat.CompilerParams(
            # the kept transform needs the output-channel tiles in order
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if cache_v else "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=f"winograd_f{m}_fused",
    )(*operands)
    return out.astype(x.dtype)
