"""Int8 x int8 -> int32 tiled GEMM — the quantized inference fast path.

A pixels-major GEMM (all three dims tiled to VMEM blocks, contraction
grid dim innermost, accumulator in VMEM scratch across C-revisits)
over int8 operands with an **int32** accumulator:
``preferred_element_type=jnp.int32`` drives the MXU's integer path, which is the "roughly double arithmetic
throughput" lever the ROADMAP names — int8 tiles are a quarter the
bytes of f32, so the same VMEM budget holds 4x the tile footprint and
the MXU runs its 8-bit mode.

The kernel returns the raw int32 accumulator; dequantization
(``acc * (x_scale * w_scale[m])``) and the fp32 epilogue are the
*executor's* job (DESIGN.md §13: requantization order), so one kernel
serves every scale layout.

Min int8 tile on TPU is (32, 128) (sublane x lane); the default blocks
are 128-multiples well above that floor.
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
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("tp", "tm", "tc", "interpret"))
def int8_gemm(x2d, w, tp=256, tm=128, tc=512, *, interpret):
    """x2d: (P, C) int8 pixels-major; w: (C, M) int8.

    Returns (P, M) **int32** — the undequantized accumulator.  Zero
    padding is exact under symmetric quantization (0 maps to code 0),
    so padded rows/columns contribute nothing to real outputs.
    """
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
        out_shape=jax.ShapeDtypeStruct((P + pp, M + pm), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tp, tm), jnp.int32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int8_gemm",
    )(xp, wp)
    return out[:P, :M]
