"""ConvNeXt-T (Liu et al. 2022, "A ConvNet for the 2020s"; torchvision
``convnext_tiny``) at 224x224, the layer scale folded into each block's
second 1x1 conv.

Three functions over the sizes in ``convnext_tiny.json``, as the other
configurations have:

- ``build(b, cfg)`` writes the network into a ``GraphBuilder`` ``b``: a
  4x4/4 patchify stem and its norm; per block a 7x7 depthwise conv, a
  norm, a 1x1 conv to 4C with the exact GELU, a 1x1 conv back to C and
  the residual add; between stages a norm and a 2x2/2 conv; GAP, a norm
  and the dense head;
- ``init(key, cfg)`` makes the weights from a key;
- ``reference(params, x, cfg)`` is the plain forward pass in
  ``jax.lax``/``jnp`` at float32 and ``HIGHEST`` precision.  It uses
  nothing of the program.

``dw_nodes(cfg)`` and ``norm_nodes(cfg)`` name the depthwise and norm
nodes, for the per-layer readers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def blocks(cfg):
    """``(name, width, stage)`` per block, stages counted from 1."""
    return [(f"s{s + 1}b{i + 1}", width, s + 1)
            for s, (n, width) in enumerate(zip(cfg["stage_depths"],
                                               cfg["stage_widths"]))
            for i in range(n)]


def dw_nodes(cfg):
    return [f"{name}dw" for name, _, _ in blocks(cfg)]


def norm_nodes(cfg):
    stages = range(2, len(cfg["stage_widths"]) + 1)
    return (["stemnorm"] + [f"{name}norm" for name, _, _ in blocks(cfg)]
            + [f"ds{s}norm" for s in stages] + ["headnorm"])


def convs(cfg):
    """``(name, k, c_in, c_out, groups, branch_end)`` per conv node."""
    widths, e = cfg["stage_widths"], cfg["expansion"]
    out = [("stem", cfg["patch_size"], cfg["in_channels"], widths[0], 1,
            False)]
    prev = 1
    for name, c, s in blocks(cfg):
        if s != prev:
            out.append((f"ds{s}", 2, widths[s - 2], c, 1, False))
            prev = s
        out += [(f"{name}dw", cfg["dw_kernel"], c, c, c, False),
                (f"{name}pw1", 1, c, e * c, 1, False),
                (f"{name}pw2", 1, e * c, c, 1, True)]
    return out


def build(b, cfg) -> None:
    eps, k, e = cfg["norm_eps"], cfg["dw_kernel"], cfg["expansion"]
    p = cfg["patch_size"]
    y = b.conv("stem", "input", p, cfg["stage_widths"][0], stride=p,
               padding=0, epilogue="bias")
    y = b.norm("stemnorm", y, eps)
    prev = 1
    for name, c, s in blocks(cfg):
        if s != prev:
            y = b.norm(f"ds{s}norm", y, eps)
            y = b.conv(f"ds{s}", y, 2, c, stride=2, padding=0,
                       epilogue="bias")
            prev = s
        z = b.conv(f"{name}dw", y, k, c, padding=k // 2, epilogue="bias",
                   groups=c)
        z = b.norm(f"{name}norm", z, eps)
        z = b.conv(f"{name}pw1", z, 1, e * c, epilogue="bias_gelu")
        z = b.conv(f"{name}pw2", z, 1, c, epilogue="bias")
        y = b.add(f"{name}add", (y, z))
    y = b.gap("gap", y)
    y = b.norm("headnorm", y, eps)
    b.dense("head", y, cfg["num_classes"])


def init(key, cfg):
    """He-normal convs; each block's second 1x1 conv (the layer scale
    folded in) scaled by 0.3; biases N(0, 0.05^2); norm scales
    1 + N(0, 0.1^2) and shifts N(0, 0.05^2)."""
    layers = convs(cfg)
    norms = norm_nodes(cfg)
    keys = iter(jax.random.split(key, 2 * (len(layers) + len(norms)) + 2))
    params = {}
    for name, k, c_in, c_out, groups, branch_end in layers:
        fan_in = k * k * c_in // groups
        std = math.sqrt(2.0 / fan_in) * (0.3 if branch_end else 1.0)
        params[name] = {
            "w": std * jax.random.normal(
                next(keys), (k, k, c_in // groups, c_out), jnp.float32),
            "b": 0.05 * jax.random.normal(next(keys), (c_out,),
                                          jnp.float32)}
    widths = dict(zip(norms, [cfg["stage_widths"][0]]
                      + [c for _, c, _ in blocks(cfg)]
                      + cfg["stage_widths"][:-1]
                      + [cfg["stage_widths"][-1]]))
    for name in norms:
        c = widths[name]
        params[name] = {
            "g": 1.0 + 0.1 * jax.random.normal(next(keys), (c,),
                                               jnp.float32),
            "b": 0.05 * jax.random.normal(next(keys), (c,), jnp.float32)}
    c = cfg["stage_widths"][-1]
    params["head"] = {
        "w": jax.random.normal(next(keys), (c, cfg["num_classes"]),
                               jnp.float32) / math.sqrt(c),
        "b": 0.05 * jax.random.normal(next(keys), (cfg["num_classes"],),
                                      jnp.float32)}
    return params


def _conv(x, p, stride, pad, groups=1):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y + p["b"]


def _norm(x, p, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def reference(params, x, cfg):
    """Logits ``(N, num_classes)`` of NHWC images ``x``."""
    eps, k = cfg["norm_eps"], cfg["dw_kernel"]
    y = _norm(_conv(x, params["stem"], cfg["patch_size"], 0),
              params["stemnorm"], eps)
    prev = 1
    for name, c, s in blocks(cfg):
        if s != prev:
            y = _conv(_norm(y, params[f"ds{s}norm"], eps),
                      params[f"ds{s}"], 2, 0)
            prev = s
        z = _conv(y, params[f"{name}dw"], 1, k // 2, groups=c)
        z = _norm(z, params[f"{name}norm"], eps)
        z = jax.nn.gelu(_conv(z, params[f"{name}pw1"], 1, 0),
                        approximate=False)
        y = y + _conv(z, params[f"{name}pw2"], 1, 0)
    y = _norm(y.mean(axis=(1, 2)), params["headnorm"], eps)
    return jnp.dot(y, params["head"]["w"], precision=HIGHEST) \
        + params["head"]["b"]
