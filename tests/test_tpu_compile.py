"""Real-width compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel at a paper width with
``interpret=False`` and compiles it for one chip of a v5e topology that
is described, not attached, so a kernel Mosaic refuses (an unsupported
primitive, a misaligned block, too much VMEM) fails here instead of on
the chip.  The topology is described inside a fixture — never at import
— and every case must find the kernel (``tpu_custom_call``) in the
compiled program.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import convspec as cs
from repro.core import executors as ex
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler / libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, BF16 = jnp.float32, jnp.bfloat16

# (x shape, w shape, padding, stride, kernel kwargs, dtype)
FUSED_CASES = {
    "t3_A-1x1": ((1, 7, 7, 832), (1, 1, 832, 256), (0, 0), (1, 1),
                 dict(tm=256, rows=7), F32),
    "t4_B-rows2": ((1, 13, 13, 384), (3, 3, 384, 384), (1, 1), (1, 1),
                   dict(tm=128, rows=2), F32),
    "stride2-3x3": ((8, 56, 56, 256), (3, 3, 256, 128), (1, 1), (2, 2),
                    dict(tm=128, rows=4), F32),
    "fused-pool": ((8, 224, 224, 3), (3, 3, 3, 16), (1, 1), (1, 1),
                   dict(tm=16, rows=16, pool=("max", 2, 2)), F32),
    "bf16-stride2": ((8, 56, 56, 64), (3, 3, 64, 128), (1, 1), (2, 2),
                     dict(tm=128, rows=2), BF16),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_cuconv_fused_compiles(one_chip, case):
    xs, ws, pad, stride, kw, dt = FUSED_CASES[case]

    def f(x, w, b):
        return ops.cuconv_fused(x, w, pad, stride=stride, bias=b,
                                activation="relu", interpret=False, **kw)
    assert "tpu_custom_call" in _compiled_text(
        f, one_chip, (xs, dt), (ws, dt), ((ws[3],), dt))


def test_cuconv_fused_residual_add_compiles(one_chip):
    xs, ws = (8, 28, 28, 128), (3, 3, 128, 128)

    def f(x, w, b, a):
        return ops.cuconv_fused(x, w, (1, 1), bias=b, activation="relu",
                                addend=a, tm=128, rows=4, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        f, one_chip, (xs, F32), (ws, F32), ((128,), F32), (xs, F32))


def _first_feasible(spec, m):
    wino = ex.get("winograd_pallas")
    return next(c for c in wino.configs(spec)
                if c["m"] == m and wino.config_supports(spec, c)[0])


def _winograd_compiled(sharding, xs, ws, cfg):
    def f(x, w, b):
        return ops.winograd_fused(x, w, (1, 1), bias=b, activation="relu",
                                  m=cfg["m"], rows=cfg["rows"],
                                  tm=cfg["tm"], tc=cfg["tc"],
                                  interpret=False)
    return _compiled_text(f, sharding, (xs, F32), (ws, F32),
                          ((ws[3],), F32))


@pytest.mark.parametrize("m", [2, 4])
def test_winograd_pallas_compiles(one_chip, m):
    """The first F(m,3) tile candidate the executor's VMEM model admits
    (F(4,3)'s 36-position domain does not fit at 128-wide tiles)."""
    xs, ws = (8, 56, 56, 256), (3, 3, 256, 256)
    spec = cs.ConvSpec(xs, ws, padding=(1, 1), epilogue="bias_relu")
    txt = _winograd_compiled(one_chip, xs, ws, _first_feasible(spec, m))
    assert "tpu_custom_call" in txt


_GATHER_OPS = re.compile(r"\b(gather|dynamic-slice)\(")


@pytest.mark.parametrize("label,xs,m_out", [
    ("resnet50-s1b1c2", (32, 56, 56, 64), 64),
    ("squeezenet1_0-fire2e3", (32, 54, 54, 16), 64),
])
def test_winograd_pallas_gathers_tiles_in_kernel(one_chip, label, xs,
                                                  m_out):
    """The benchmark's widest Winograd shapes, at the executor's first
    feasible config: the tiles are formed in VMEM from the phase-split
    input, so outside the kernel the compiled program holds no gather
    and no dynamic-slice (the XLA tile gather this kernel replaced)."""
    ws = (3, 3, xs[3], m_out)
    spec = cs.ConvSpec(xs, ws, padding=(1, 1), epilogue="bias_relu")
    txt = _winograd_compiled(one_chip, xs, ws, _first_feasible(spec, 2))
    assert "tpu_custom_call" in txt
    outside = [line for line in txt.splitlines()
               if "tpu_custom_call" not in line]
    found = [line.strip()[:160] for line in outside
             if _GATHER_OPS.search(line)]
    assert not found, f"{label}: gather outside the kernel: {found}"


def test_int8_gemm_compiles(one_chip):
    def f(x, w):
        return ops.int8_gemm(x, w, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        f, one_chip, ((8 * 28 * 28, 9 * 128), jnp.int8),
        ((9 * 128, 128), jnp.int8))


@pytest.mark.parametrize("label", ["t4_A", "t5_B", "vgg19-224-64-3",
                                   "resnet50-7-512-512"])
def test_tpu_planned_default_config_compiles(one_chip, label):
    """What ``plan(backend="tpu")`` picks — executor and model-chosen
    launch config — compiles, and is a Pallas kernel where claimed."""
    spec = {
        "t4_A": cs.ConvSpec((1, 7, 7, 192), (3, 3, 192, 384),
                            padding=(1, 1), epilogue="bias_relu"),
        "t5_B": cs.ConvSpec((8, 7, 7, 48), (5, 5, 48, 128),
                            padding=(2, 2), epilogue="bias_relu"),
        "vgg19-224-64-3": cs.ConvSpec((8, 224, 224, 3), (3, 3, 3, 64),
                                      padding=(1, 1), epilogue="bias_relu"),
        "resnet50-7-512-512": cs.ConvSpec((8, 7, 7, 512), (3, 3, 512, 512),
                                          padding=(1, 1),
                                          epilogue="bias_relu"),
    }[label]
    p = dataclasses.replace(cs.plan(spec, backend="tpu"), interpret=False)
    assert p.source != "fallback"
    txt = _compiled_text(p, one_chip, (spec.in_shape, F32),
                         (spec.filter_shape, F32),
                         ((spec.filter_shape[3],), F32))
    if ex.get(p.algorithm).takes_interpret:
        assert "tpu_custom_call" in txt


@pytest.mark.parametrize("label", [
    "convnext-s1dw-b32", "convnext-s2dw-b32", "convnext-s3dw-b32",
    "convnext-s4dw-b8", "convnext-s1pw1-gelu-b32", "convnext-s3pw1-gelu-b8"])
def test_tpu_planned_convnext_nodes_compile(one_chip, label):
    """ConvNeXt-T's depthwise and GELU-epilogue nodes as
    ``plan(backend="tpu")`` plans them, at published widths."""
    def dw(n, h, c):
        return cs.ConvSpec((n, h, h, c), (7, 7, 1, c), padding=(3, 3),
                           epilogue="bias", groups=c)

    def pw1(n, h, c):
        return cs.ConvSpec((n, h, h, c), (1, 1, c, 4 * c),
                           epilogue="bias_gelu")
    spec = {"convnext-s1dw-b32": dw(32, 56, 96),
            "convnext-s2dw-b32": dw(32, 28, 192),
            "convnext-s3dw-b32": dw(32, 14, 384),
            "convnext-s4dw-b8": dw(8, 7, 768),
            "convnext-s1pw1-gelu-b32": pw1(32, 56, 96),
            "convnext-s3pw1-gelu-b8": pw1(8, 14, 384)}[label]
    p = dataclasses.replace(cs.plan(spec, backend="tpu"), interpret=False)
    assert p.source != "fallback"
    txt = _compiled_text(p, one_chip, (spec.in_shape, F32),
                         (spec.filter_shape, F32),
                         ((spec.filter_shape[3],), F32))
    if ex.get(p.algorithm).takes_interpret:
        assert "tpu_custom_call" in txt
