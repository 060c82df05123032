"""cuConv stage 2 (faithful): sum the KH*KW per-tap partial matrices.

The CUDA `sum_kernel` gathers one element from each of the KH*KW
temporary matrices per output element.  TPU mapping: the tap axis is the
*sublane-major* axis of a (T, tile_p, tile_m) VMEM block, reduced with a
single vector-add tree per block — purely bandwidth-bound, exactly like
the original (paper tables 4/5 show stage 2 at 1-9% of total time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat


def _kernel(t_ref, o_ref):
    o_ref[...] = jnp.sum(t_ref[...].astype(jnp.float32), axis=0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tp", "tm", "out_dtype",
                                             "interpret"))
def stage2_tap_sum(temps, tp=256, tm=256, out_dtype=jnp.float32, *,
                   interpret):
    """temps: (T, P, M) stage-1 partials -> (P, M) output plane sums."""
    T, P, M = temps.shape
    (tp, tm), (pp, pm) = _compat.clamp_tiles((P, M), (tp, tm))
    tpad = jnp.pad(temps, ((0, 0), (0, pp), (0, pm)))
    grid = ((P + pp) // tp, (M + pm) // tm)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((T, tp, tm), lambda p, m: (0, p, m))],
        out_specs=pl.BlockSpec((tp, tm), lambda p, m: (p, m)),
        out_shape=jax.ShapeDtypeStruct((P + pp, M + pm), out_dtype),
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="cuconv_stage2",
    )(tpad)
    return out[:P, :M]
