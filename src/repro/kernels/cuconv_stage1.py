"""cuConv stage 1 (faithful): per-tap channel contraction.

The CUDA kernel (`scalar_prods_kernel`) pins one filter row in shared
memory and streams the input rows that reuse it.  TPU mapping: each grid
step pins one filter-tap block F[t] (C_tile x M_tile) in VMEM and streams
a pixel tile of the tap's shifted input view against it on the MXU —
same reuse structure, systolic instead of scalar.

Inputs are the KH*KW shifted views stacked by the wrapper (XLA slices of
the padded input — *not* an im2col matrix; element duplication never hits
HBM as the views alias the same buffer until fused by XLA).
Output: the paper's temporaries (T, P, M) — deliberately materialized,
that is the faithful-memory-behaviour variant benchmarked against the
fused kernel in §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat


def _kernel(xs_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(xs_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tp", "tm", "tc", "interpret"))
def stage1_tap_gemm(xs, w, tp=256, tm=128, tc=512, *, interpret):
    """xs: (T, P, C) stacked shifted views; w: (T, C, M) filter taps.

    Returns the stage-1 temporaries (T, P, M), f32.
    """
    T, P, C = xs.shape
    _, _, M = w.shape
    (tp, tm, tc), (pp, pm, pc) = _compat.clamp_tiles((P, M, C),
                                                     (tp, tm, tc))
    xsp = jnp.pad(xs, ((0, 0), (0, pp), (0, pc)))
    wp = jnp.pad(w, ((0, 0), (0, pc), (0, pm)))
    grid = (T, (P + pp) // tp, (M + pm) // tm, (C + pc) // tc)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp, tc), lambda t, p, m, c: (t, p, c)),
            pl.BlockSpec((1, tc, tm), lambda t, p, m, c: (t, c, m)),
        ],
        out_specs=pl.BlockSpec((1, tp, tm), lambda t, p, m, c: (t, p, m)),
        out_shape=jax.ShapeDtypeStruct((T, P + pp, M + pm), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tp, tm), jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="cuconv_stage1",
    )(xsp, wp)
    return out[:, :P, :M]
