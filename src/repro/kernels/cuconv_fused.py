"""Fused cuConv: both stages in one kernel (beyond-paper optimization).

The paper's future-work section proposes "work-fusion".  On TPU the
Pallas grid-revisiting model makes it natural: the tap axis is the
innermost ("arbitrary") grid dimension, the output block's index_map
ignores it, so the output block stays resident in VMEM across all KH*KW
taps and the per-tap partials are accumulated *in registers/VMEM* instead
of round-tripping (KH*KW x output-size) temporaries through HBM.

Napkin math (7x7x832 in, 3x3 filter, M=384, f32 — paper table 4 "A"):
  two-stage HBM traffic: stage-1 write 9*49*384*4 = 677 KB/input
                       + stage-2 read  677 KB + write 75 KB
  fused:                 write 75 KB/input  (≈ 18x less output traffic)
Stage 1 dominates cuConv time in the paper (91-99 %); killing the
temporary stream attacks its memory term directly.

Launch configuration (DESIGN.md §9): the kernel geometry is *tunable* —
``tm`` is the output-channel tile, ``rows`` the number of output rows
each grid step produces.  Grid: (N, ceil(OH/rows), M_tiles, KH*KW).
``rows >= 2`` lets the short-``OW`` paper shapes (7x7, 13x13) feed the
MXU more than one output row per step.

Input layout: the padded input is split into stride phases
(``_compat.phase_split``), so tap ``(di, dj)`` reads phase
``(di % sh, dj % sw)`` at offset ``(di // sh, dj // sw)`` with unit
stride and the kernel needs no strided load.  Each grid step's input
block is the halo'd band of ``rows + (KH-1)//sh`` phase rows its
output rows read, addressed by an
*element* offset (``pl.Element``) in the index_map: it does not depend
on the tap or the channel tile, so it is fetched once per output-row
block and stays resident across all KH*KW taps.

The tap's ``(rows, OW, C)`` window is a ref slice,
``x_ref[0, di % sh, pl.ds(di // sh, rows), dj % sw, pl.ds(dj // sw, OW)]``
— no gather and no value-level dynamic slice (Mosaic lowers neither).
The row offset is dynamic (a leading dim); the column offset is made
static by one ``pl.when`` branch per filter column, because Mosaic
refuses a dynamic unaligned offset on the sublane dim.  The window hits
the MXU as one ``(rows*OW x C) @ (C x TM)`` matmul.

Epilogue (DESIGN.md §4): on the final tap the still-VMEM-resident
accumulator takes bias add + activation before the single HBM write —
``relu(conv(x, w) + b)`` costs no extra HBM round trip.

Cross-layer fusion (DESIGN.md §10) extends the same epilogue slot:

``addend`` — a residual second operand (shape == the conv output) whose
block rides the output's index_map, added after the bias and before the
activation, so a ResNet shortcut join (``relu(conv(x) + b + shortcut)``)
also costs no extra HBM round trip.

``pool`` — a trailing non-overlapping max/avg pool ``(kind, psh, psw)``
(window == stride, no padding): the conv partials accumulate in an f32
VMEM *scratch* block of ``rows`` output rows; on the final tap the
epilogue runs in place and the block is pooled with strided scratch
reads down to ``(rows/psh, OW/psw)`` before the single — now
pool-sized — HBM write.  Validity (``config_supports`` on the executor
enforces it): ``rows % psh == 0``, ``OH % rows == 0``, ``OW % psw == 0``,
and ``tm <= 128`` (the strided scratch reads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compat


def _make_kernel(kw: int, ow: int, sh: int, sw: int, rows: int, taps: int,
                 activation, has_bias: bool, has_add: bool, pool=None):
    def _kernel(*refs):
        refs = list(refs)
        x_ref, w_ref = refs.pop(0), refs.pop(0)
        b_ref = refs.pop(0) if has_bias else None
        a_ref = refs.pop(0) if has_add else None
        o_ref = refs.pop(0)
        acc_ref = refs.pop(0) if pool is not None else o_ref.at[0]
        t = pl.program_id(3)
        di = t // kw
        dj = jax.lax.rem(t, kw)

        def _tap(j):
            win = x_ref[0, jax.lax.rem(di, sh), pl.ds(di // sh, rows),
                        j % sw, pl.ds(j // sw, ow), :]      # (rows, OW, C)
            win = win.reshape(rows * ow, win.shape[-1])
            part = jnp.dot(win, w_ref[0, 0],
                           preferred_element_type=jnp.float32)
            part = part.reshape(rows, ow, part.shape[-1])   # (rows, OW, TM)

            # conv partials accumulate in VMEM: the output block
            # (revisited across all taps) or, under a fused pool, the
            # f32 scratch; t == 0 implies dj == 0
            if j == 0:
                @pl.when(t == 0)
                def _init():
                    acc_ref[...] = part

                @pl.when(t > 0)
                def _acc0():
                    acc_ref[...] += part
            else:
                acc_ref[...] += part

        for j in range(kw):
            pl.when(dj == j)(functools.partial(_tap, j))

        if not (has_bias or has_add or activation is not None
                or pool is not None):
            return

        @pl.when(t == taps - 1)
        def _epilogue():
            acc = acc_ref[...]
            if has_bias:
                acc = acc + b_ref[0].astype(jnp.float32)
            if has_add:
                acc = acc + a_ref[0].astype(jnp.float32)
            if activation == "relu":
                acc = jnp.maximum(acc, 0.0)
            acc_ref[...] = acc
            if pool is not None:
                o_ref[0] = _pool_block(acc_ref, rows, ow, *pool)

    return _kernel


def _pool_block(acc_ref, rows: int, ow: int, kind: str, psh: int, psw: int):
    """Non-overlapping (window == stride) pool of the (rows, OW, TM) f32
    scratch via strided ref reads — no gather, TPU-legal."""
    pooled = None
    for i in range(psh):
        for j in range(psw):
            piece = acc_ref[pl.ds(i, rows // psh, stride=psh),
                            pl.ds(j, ow // psw, stride=psw), :]
            if pooled is None:
                pooled = piece
            elif kind == "max":
                pooled = jnp.maximum(pooled, piece)
            else:
                pooled = pooled + piece
    if kind == "avg":
        pooled = pooled / (psh * psw)
    return pooled


def _phase_extents(H, W, KH, KW, stride, padding, rows):
    """``(OH, OW, OHB, band, Hq, Wq)``: output extents, output-row
    blocks, phase rows per block, and the phase-split input extents."""
    sh, sw = stride
    OH = (H + 2 * padding[0] - KH) // sh + 1
    OW = (W + 2 * padding[1] - KW) // sw + 1
    OHB = -(-OH // rows)
    # the last block's band reaches output row OHB*rows - 1: the rows
    # past OH are zeros and feed only outputs that are sliced away
    Hq, Wq = _compat.phase_extents(H, W, KH, KW, stride, padding,
                                   OHB * rows, OW)
    return OH, OW, OHB, rows + (KH - 1) // sh, Hq, Wq


@functools.partial(jax.jit, static_argnames=("stride", "padding",
                                             "activation", "pool",
                                             "tm", "rows", "interpret"))
def cuconv_fused(x, w, bias=None, stride=(1, 1), padding=(0, 0),
                 activation=None, addend=None, pool=None,
                 tm=128, rows=1, *, interpret):
    """x: (N, H, W, C) NHWC; w: (KH, KW, C, M) HWIO; stride (sh, sw) >= 1.

    bias: optional (M,) added on the final tap; activation: None | 'relu',
    applied after bias — both fused in VMEM before the output write.
    addend: optional (N, OH, OW, M) residual operand added after the
    bias and before the activation (cross-layer add fusion).  pool:
    optional ``(kind, psh, psw)`` non-overlapping max/avg pool (window
    == stride, no padding) applied to the finished block in VMEM before
    writeback; mutually exclusive with ``addend``.
    ``tm``/``rows`` are the launch configuration (output-channel tile and
    output rows per grid step).  ``pool`` additionally needs
    ``rows % psh == 0``, ``OH % rows == 0`` and ``OW % psw == 0``.
    ``interpret`` is required: callers resolve it per backend
    (``kernels.ops``).
    Returns (N, OH, OW, M) — pooled to (N, OH/psh, OW/psw, M) under
    ``pool`` — in x.dtype.
    """
    N, H, W, C = x.shape
    KH, KW, _, M = w.shape
    sh, sw = stride
    rows = min(int(rows), (H + 2 * padding[0] - KH) // sh + 1)
    if rows < 1:
        raise ValueError(f"rows must be >= 1; got {rows}")
    OH, OW, OHB, band, Hq, Wq = _phase_extents(H, W, KH, KW, stride,
                                               padding, rows)
    if pool is not None:
        if addend is not None:
            raise ValueError("pool and addend fusions are mutually "
                             "exclusive (ConvSpec enforces this)")
        kind, psh, psw = pool
        if kind not in ("max", "avg"):
            raise ValueError(f"pool kind must be 'max' or 'avg'; "
                             f"got {pool!r}")
        if rows % psh or OH % rows or OW % psw:
            raise ValueError(
                f"fused pool needs rows % psh == 0, OH % rows == 0 and "
                f"OW % psw == 0; got rows={rows}, OH={OH}, OW={OW}, "
                f"pool={pool!r}")
    if addend is not None and addend.shape != (N, OH, OW, M):
        raise ValueError(f"addend shape {addend.shape} != conv output "
                         f"shape {(N, OH, OW, M)}")
    (tm,), (pm,) = _compat.clamp_tiles((M,), (tm,))
    xq = _compat.phase_split(x, stride, padding, Hq, Wq)
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pm)))
    grid = (N, OHB, (M + pm) // tm, KH * KW)
    in_specs = [
        # the halo'd band of every phase at element offset oh*rows:
        # resident across every tap and channel tile of this block
        # (Mosaic takes element indexing on all dims or none)
        pl.BlockSpec((pl.Element(1), pl.Element(sh), pl.Element(band),
                      pl.Element(sw), pl.Element(Wq), pl.Element(C)),
                     lambda n, oh, m, t: (n, 0, oh * rows, 0, 0, 0)),
        # the tap matrix F[di, dj] (C x TM)
        pl.BlockSpec((1, 1, C, tm),
                     lambda n, oh, m, t: (t // KW, jax.lax.rem(t, KW),
                                          0, m)),
    ]
    operands = [xq, wp]
    if bias is not None:
        bp = jnp.pad(bias.reshape(1, M), ((0, 0), (0, pm)))
        in_specs.append(pl.BlockSpec((1, tm), lambda n, oh, m, t: (0, m)))
        operands.append(bp)
    if addend is not None:
        # OH padded up to the block grid so the last step's residual
        # block exists; the padded rows feed outputs sliced away below
        ap = jnp.pad(addend, ((0, 0), (0, OHB * rows - OH), (0, 0),
                              (0, pm)))
        in_specs.append(pl.BlockSpec((1, rows, OW, tm),
                                     lambda n, oh, m, t: (n, oh, 0, m)))
        operands.append(ap)
    kernel = _make_kernel(KW, OW, sh, sw, rows, KH * KW, activation,
                          bias is not None, addend is not None, pool)
    if pool is None:
        # the (rows, OW, TM) output block is revisited across all taps
        out_rows, out_w, scratch = rows, OW, []
    else:
        # the output block is the POOLED tile; conv partials accumulate
        # in the f32 scratch instead
        out_rows, out_w = rows // psh, OW // psw
        scratch = [pltpu.VMEM((rows, OW, tm), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_rows, out_w, tm),
                               lambda n, oh, m, t: (n, oh, 0, m)),
        out_shape=jax.ShapeDtypeStruct((N, OHB * out_rows, out_w, M + pm),
                                       jnp.float32),
        scratch_shapes=scratch,
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="cuconv_fused",
    )(*operands)
    out_h = OH if pool is None else OH // psh
    return out[:, :out_h, :, :M].astype(x.dtype)


def vmem_bytes(x_shape, w_shape, tm=128, rows=1, pad=(0, 0), stride=(1, 1),
               itemsize=4, addend=False, pool=None):
    """Static VMEM footprint estimate for the fused kernel under launch
    config ``(tm, rows)``, at the TPU's tiled layout
    (``_compat.tiled_bytes``).

    Counts the double-buffered input band, tap matrix, output block and
    ``addend`` block, plus the in-kernel values a step holds: the
    ``(rows*OW, C)`` window and its ``(rows*OW, TM)`` f32 partial.
    ``pool`` — ``(kind, psh, psw)`` — adds the f32 scratch accumulator;
    the output block is then the pooled tile.
    """
    _, H, W, C = x_shape
    KH, KW, _, M = w_shape
    sh, sw = stride
    rows = max(1, min(int(rows), (H + 2 * pad[0] - KH) // sh + 1))
    tm = min(int(tm), M)
    _, OW, _, band, _, Wq = _phase_extents(H, W, KH, KW, stride, pad, rows)
    need = 2 * sh * sw * _compat.tiled_bytes((band, Wq, C), itemsize)
    need += 2 * _compat.tiled_bytes((C, tm), itemsize)
    need += (_compat.tiled_bytes((rows * OW, C), itemsize)
             + _compat.tiled_bytes((rows * OW, tm), 4))
    out_rows, out_w = rows, OW
    if pool is not None:
        _, psh, psw = pool
        need += _compat.tiled_bytes((rows, OW, tm), 4)
        out_rows, out_w = rows // max(1, psh), OW // max(1, psw)
    need += 2 * _compat.tiled_bytes((out_rows, out_w, tm), 4)
    if addend:
        need += 2 * _compat.tiled_bytes((rows, OW, tm), itemsize)
    return need
