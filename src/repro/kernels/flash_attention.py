"""Blockwise (flash) attention forward kernel.

Not a paper contribution — it is the perf-critical layer of the LM
substrate the framework serves/trains.  Online-softmax recurrence over
KV tiles; the KV grid dim is innermost/arbitrary so the accumulator,
running max m and denominator l stay VMEM-resident per query tile.

Scratch uses (tq, 1)-shaped m/l for clarity; a production TPU build
would lane-replicate to (tq, 128) to avoid sublane relayouts.  Causal
query tiles entirely below the diagonal skip compute via pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import _compat

NEG_INF = -1e30


def _make_kernel(tq: int, tk: int, sk_real: int, causal: bool):
    def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        iq, ik = pl.program_id(1), pl.program_id(2)
        qo = iq * tq
        ko = ik * tk

        @pl.when(ik == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # skip KV tiles strictly above the causal diagonal
        run = (ko <= qo + tq - 1) if causal else True

        @pl.when(run)
        def _step():
            q = q_ref[0]                                    # (tq, D)
            k = k_ref[0]                                    # (tk, D)
            v = v_ref[0]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s *= 1.0 / (q.shape[-1] ** 0.5)
            kv_idx = ko + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            valid = kv_idx < sk_real
            if causal:
                q_idx = qo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
                valid = valid & (q_idx >= kv_idx)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[...]                             # (tq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        @pl.when(ik == pl.num_programs(2) - 1)
        def _done():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("causal", "tq", "tk", "interpret"))
def flash_attention(q, k, v, causal=True, tq=256, tk=256, *, interpret):
    """q: (BH, Sq, D); k, v: (BH, Sk, D).  Softmax(QK^T/sqrt(D))V."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    tq, tk = min(tq, Sq), min(tk, Sk)
    pq, pk = (-Sq) % tq, (-Sk) % tk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    grid = (BH, (Sq + pq) // tq, (Sk + pk) // tk)
    out = pl.pallas_call(
        _make_kernel(tq, tk, Sk, causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq + pq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, D), jnp.float32),
        ],
        compiler_params=_compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :Sq, :]
