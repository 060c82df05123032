"""1x1 convolution = pointwise GEMM — the paper's best-case fast path.

A 1x1 convolution has a single filter tap, so cuConv stage 1 *is* the
convolution (paper §3: "the second kernel is not necessary").  On TPU this
is a plain tiled matmul on the MXU: (pixels x C) @ (C x M), with all three
dims tiled to VMEM blocks and the C (contraction) grid dim innermost so
the f32 accumulator lives in VMEM scratch across revisits.

Block shape rationale (v5e): 256x512 x-block (512 KB f32), 512x128 w-block
(256 KB), 256x128 acc (128 KB) — three buffers + double buffering stay
well inside the ~16 MB hull; 128-multiples keep the MXU fully fed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tp", "tm", "tc", "interpret"))
def conv1x1_gemm(x2d, w, tp=256, tm=128, tc=512, *, interpret):
    """x2d: (P, C) pixels-major; w: (C, M).  Returns (P, M) in x2d.dtype."""
    P, C = x2d.shape
    _, M = w.shape
    (tp, tm, tc), (pp, pm, pc) = _compat.clamp_tiles((P, M, C),
                                                     (tp, tm, tc))
    xp = jnp.pad(x2d, ((0, pp), (0, pc)))
    wp = jnp.pad(w, ((0, pc), (0, pm)))
    grid = ((P + pp) // tp, (M + pm) // tm, (C + pc) // tc)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tp, tc), lambda p, m, c: (p, c)),
            pl.BlockSpec((tc, tm), lambda p, m, c: (c, m)),
        ],
        out_specs=pl.BlockSpec((tp, tm), lambda p, m, c: (p, m)),
        out_shape=jax.ShapeDtypeStruct((P + pp, M + pm), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((tp, tm), jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="conv1x1_gemm",
    )(xp, wp)
    return out[:P, :M]
