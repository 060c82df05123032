"""Im2col-free direct convolution with channel-tiled VMEM accumulation.

The explicit-GEMM path (cuDNN "GEMM", our im2col executor) buys one big
MXU matmul by materializing the KH*KW-duplicated patch matrix through
HBM — ``2 * N*OH*OW*KH*KW*C * itemsize`` of extra traffic, the exact
overhead Li et al. ("A Memory-Efficient Direct Convolution...",
arXiv:1610.03618) eliminate.  This kernel is that memory-efficiency
lever as a Pallas executor: no patch matrix, no per-tap HBM
temporaries — the input is read once per output-channel tile, and the
KH*KW tap contributions for one *channel tile* accumulate into an fp32
VMEM scratch across contraction grid steps.

Grid: ``(N, M/tm, C/tc)`` with the channel contraction innermost
("arbitrary").  Each step stages one image's padded spatial extent for
a ``tc``-channel slice, split into stride phases
(``_compat.phase_split``), plus the matching (KH, KW, tc, tm) filter
block, unrolls the KH*KW taps as unit-stride ref windows feeding
``(OH*OW x tc) @ (tc x tm)`` MXU matmuls, and writes the output block
once on the final channel step.  Because C is tiled, the VMEM working
set is bounded no matter how many input channels the spec has — the
large-C region where the full-C row staging of the fused kernel and
the patch matrix of im2col both blow up.

Tuning dims (the direct executor's launch-config space): ``tm``
(output-channel tile), ``tc`` (input-channel tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat


def _make_kernel(KH, KW, OH, OW, sh, sw):
    def kernel(x_ref, w_ref, o_ref, acc_ref):
        c = pl.program_id(2)
        part = None
        for i in range(KH):
            for j in range(KW):
                # tap (i, j) reads its stride phase with unit stride
                win = x_ref[0, i % sh, pl.ds(i // sh, OH),
                            j % sw, pl.ds(j // sw, OW), :]  # (OH, OW, tc)
                t = jnp.dot(win.reshape(OH * OW, win.shape[-1]),
                            w_ref[i, j], preferred_element_type=jnp.float32)
                part = t if part is None else part + t

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(c > 0)
        def _accumulate():
            acc_ref[...] += part

        @pl.when(c == pl.num_programs(2) - 1)
        def _done():
            o_ref[0] = acc_ref[...].reshape(
                OH, OW, acc_ref.shape[-1]).astype(o_ref.dtype)

    return kernel


def _extents(H, W, KH, KW, stride, pad):
    """``(OH, OW, Hq, Wq)``: output extents and phase-split input
    extents (``_compat.phase_split``)."""
    OH = (H + 2 * pad[0] - KH) // stride[0] + 1
    OW = (W + 2 * pad[1] - KW) // stride[1] + 1
    return (OH, OW) + _compat.phase_extents(H, W, KH, KW, stride, pad,
                                            OH, OW)


def vmem_bytes(in_shape, filter_shape, stride=(1, 1), pad=(0, 0),
               tm=128, tc=256, itemsize=4):
    """Live-block VMEM model of one grid step at the TPU's tiled layout
    (``_compat.tiled_bytes``): the channel-sliced image and filter
    blocks double buffered, the output block double buffered, the fp32
    accumulator, and the in-kernel tap window and partial sum."""
    _, H, W_, C = in_shape
    KH, KW, _, M = filter_shape
    tm, tc = min(tm, M), min(tc, C)
    OH, OW, Hq, Wq = _extents(H, W_, KH, KW, stride, pad)
    tb = _compat.tiled_bytes
    return int(2 * stride[0] * stride[1] * tb((Hq, Wq, tc), itemsize)
               + 2 * tb((KH, KW, tc, tm), itemsize)
               + 2 * tb((OH, OW, tm), itemsize)
               + tb((OH * OW, tm), 4)                    # accumulator
               + tb((OH * OW, tc), itemsize)             # tap window
               + tb((OH * OW, tm), 4))                   # partial sum


@functools.partial(jax.jit, static_argnames=(
    "padding", "stride", "tm", "tc", "interpret"))
def direct_conv(x, w, padding=(0, 0), stride=(1, 1), tm=128, tc=256, *,
                interpret):
    """x: (N, H, W, C) NHWC; w: (KH, KW, C, M) HWIO; any stride.

    Bare conv (no epilogue — the direct executor is non-fusing, so
    bias/activation/fusions apply as XLA ops downstream).  ``interpret``
    is required: callers resolve it per backend (``kernels.ops``).
    Returns (N, OH, OW, M) in ``x.dtype``.
    """
    N, H, W_, C = x.shape
    KH, KW, _, M = w.shape
    sh, sw = stride
    OH, OW, Hq, Wq = _extents(H, W_, KH, KW, stride, padding)
    (tm, tc), (pm, pc) = _compat.clamp_tiles((M, C), (tm, tc))
    xq = _compat.phase_split(jnp.pad(x, ((0, 0),) * 3 + ((0, pc),)),
                             stride, padding, Hq, Wq)
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, pc), (0, pm)))
    grid = (N, (M + pm) // tm, (C + pc) // tc)
    out = pl.pallas_call(
        _make_kernel(KH, KW, OH, OW, sh, sw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, sh, Hq, sw, Wq, tc),
                         lambda n, mo, c: (n, 0, 0, 0, 0, c)),
            pl.BlockSpec((KH, KW, tc, tm), lambda n, mo, c: (0, 0, c, mo)),
        ],
        out_specs=pl.BlockSpec((1, OH, OW, tm),
                               lambda n, mo, c: (n, 0, 0, mo)),
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, M + pm), x.dtype),
        scratch_shapes=[pltpu.VMEM((OH * OW, tm), jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="direct_conv",
    )(xq, wp)
    return out[..., :M]
