"""ResNet-50 v1.5 (He et al. 2016; torchvision ``resnet50``), BN folded.

Three functions over the sizes in ``resnet50.json``:

- ``build(b, cfg)`` writes the network into a ``GraphBuilder`` ``b``
  (the program's graph IR), node by node;
- ``init(key, cfg)`` makes the weights from a key (the harness jits it,
  so they are made on the device in one call);
- ``reference(params, x, cfg)`` is the plain forward pass in
  ``jax.lax`` at float32 and ``HIGHEST`` precision.  It uses nothing of
  the program.

All three walk ``blocks(cfg)``, so the node names (and param keys)
agree.  BatchNorm is folded into each conv's bias, as inference
deployments do.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def blocks(cfg):
    """``(name, c_in, width, c_out, stride, projection)`` per bottleneck."""
    c_in = cfg["stem_channels"]
    out = []
    for s, (n, width) in enumerate(zip(cfg["stage_blocks"],
                                       cfg["stage_widths"])):
        c_out = width * cfg["expansion"]
        for i in range(n):
            stride = 2 if (i == 0 and s > 0) else 1
            out.append((f"s{s + 1}b{i + 1}", c_in, width, c_out, stride,
                        i == 0))
            c_in = c_out
    return out


def convs(cfg):
    """``(name, k, c_in, c_out, branch_end)`` for every conv node."""
    k = cfg["stem_kernel"]
    out = [("stem", k, cfg["in_channels"], cfg["stem_channels"], False)]
    for name, c_in, width, c_out, _, proj in blocks(cfg):
        if proj:
            out.append((f"{name}proj", 1, c_in, c_out, True))
        out += [(f"{name}c1", 1, c_in, width, False),
                (f"{name}c2", 3, width, width, False),
                (f"{name}c3", 1, width, c_out, True)]
    return out


def build(b, cfg) -> None:
    y = b.conv("stem", "input", cfg["stem_kernel"], cfg["stem_channels"],
               stride=cfg["stem_stride"])
    sp = cfg["stem_pool"]
    y = b.pool("pool", y, kind="max", window=sp["window"],
               stride=sp["stride"], padding=sp["padding"])
    for name, _, width, c_out, stride, proj in blocks(cfg):
        short = (b.conv(f"{name}proj", y, 1, c_out, stride=stride,
                        epilogue="bias") if proj else y)
        z = b.conv(f"{name}c1", y, 1, width)
        z = b.conv(f"{name}c2", z, 3, width, stride=stride)
        z = b.conv(f"{name}c3", z, 1, c_out, epilogue="bias")
        y = b.add(f"{name}add", (short, z), activation="relu")
    y = b.gap("gap", y)
    b.dense("head", y, cfg["num_classes"])


def init(key, cfg):
    """He-normal convs; the last conv of each branch (and each
    projection) scaled by 0.3 so 16 residual adds keep activations O(1);
    nonzero biases so the bias path is checked too."""
    layers = convs(cfg)
    keys = jax.random.split(key, 2 * len(layers) + 2)
    params = {}
    for i, (name, k, c_in, c_out, branch_end) in enumerate(layers):
        std = math.sqrt(2.0 / (k * k * c_in)) * (0.3 if branch_end else 1.0)
        params[name] = {
            "w": std * jax.random.normal(keys[2 * i], (k, k, c_in, c_out),
                                         jnp.float32),
            "b": 0.05 * jax.random.normal(keys[2 * i + 1], (c_out,),
                                          jnp.float32)}
    c_in = cfg["stage_widths"][-1] * cfg["expansion"]
    params["head"] = {
        "w": jax.random.normal(keys[-2], (c_in, cfg["num_classes"]),
                               jnp.float32) / math.sqrt(c_in),
        "b": 0.05 * jax.random.normal(keys[-1], (cfg["num_classes"],),
                                      jnp.float32)}
    return params


def _conv(x, p, stride, pad):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["b"]


def reference(params, x, cfg):
    """Logits ``(N, num_classes)`` of NHWC images ``x``."""
    k = cfg["stem_kernel"]
    y = jax.nn.relu(_conv(x, params["stem"], cfg["stem_stride"], k // 2))
    sp = cfg["stem_pool"]
    p = sp["padding"]
    y = lax.reduce_window(y, -jnp.inf, lax.max,
                          (1, sp["window"], sp["window"], 1),
                          (1, sp["stride"], sp["stride"], 1),
                          ((0, 0), (p, p), (p, p), (0, 0)))
    for name, _, _, _, stride, proj in blocks(cfg):
        short = (_conv(y, params[f"{name}proj"], stride, 0) if proj else y)
        z = jax.nn.relu(_conv(y, params[f"{name}c1"], 1, 0))
        z = jax.nn.relu(_conv(z, params[f"{name}c2"], stride, 1))
        z = _conv(z, params[f"{name}c3"], 1, 0)
        y = jax.nn.relu(short + z)
    y = y.mean(axis=(1, 2))
    return jnp.dot(y, params["head"]["w"], precision=HIGHEST) \
        + params["head"]["b"]
