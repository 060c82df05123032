"""Tap-decomposed depthwise causal conv1d (cuConv's idea in 1D).

Used by the Mamba2 / Jamba SSM blocks (d_conv = 4).  Depthwise conv has
no channel contraction, so taps accumulate on the VPU (elementwise FMA)
instead of the MXU — the decomposition still removes any im2col-style
window materialization: the K shifted views are XLA slices of one padded
buffer, and the kernel accumulates K rank-1-broadcast FMAs per tile with
the output tile resident in VMEM (tap axis innermost, revisited).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat


def _kernel(xs_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += (xs_ref[0].astype(jnp.float32)
                     * w_ref[0].astype(jnp.float32)[None, :])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tl", "td", "interpret"))
def conv1d_tap(x, w, b=None, tl=512, td=256, *, interpret):
    """Causal depthwise conv1d.  x: (B, L, D); w: (K, D); b: (D,) or None."""
    B, Lx, D = x.shape
    K, _ = w.shape
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # K shifted views, flattened over (B, L)
    xs = jnp.stack([xp[:, k:k + Lx, :] for k in range(K)], axis=0)
    xs = xs.reshape(K, B * Lx, D)
    P = B * Lx
    tl, td = min(tl, P), min(td, D)
    pp, pd = (-P) % tl, (-D) % td
    xsp = jnp.pad(xs, ((0, 0), (0, pp), (0, pd)))
    wp = jnp.pad(w, ((0, 0), (0, pd)))
    grid = ((P + pp) // tl, (D + pd) // td, K)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tl, td), lambda p, d, k: (k, p, d)),
            pl.BlockSpec((1, td), lambda p, d, k: (k, d)),
        ],
        out_specs=pl.BlockSpec((tl, td), lambda p, d, k: (p, d)),
        out_shape=jax.ShapeDtypeStruct((P + pp, D + pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((tl, td), jnp.float32)],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="conv1d_tap",
    )(xsp, wp)
    out = out[:P, :D].reshape(B, Lx, D)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out
