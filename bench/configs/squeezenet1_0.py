"""SqueezeNet 1.0 (Iandola et al. 2016; torchvision ``squeezenet1_0``).

The same three functions as ``resnet50.py`` (``build``, ``init``,
``reference``) over the sizes in ``squeezenet1_0.json``.  ``reference``
is plain ``jax.lax`` at float32 and ``HIGHEST`` precision and uses
nothing of the program.  The classifier is conv10 (1x1 to the classes,
ReLU) and a global average pool; dropout is the identity at inference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def layers(cfg):
    """The network as a list: ``("pool", padding)`` or
    ``("fire", name, c_in, squeeze, expand1, expand3)``."""
    out = []
    c_in = cfg["conv1_channels"]
    pools = iter(cfg["pool_padding"])
    out.append(("pool", next(pools)))
    n = 2
    for f in cfg["fires"]:
        if f == "pool":
            out.append(("pool", next(pools)))
            continue
        s, e1, e3 = f
        out.append(("fire", f"fire{n}", c_in, s, e1, e3))
        c_in = e1 + e3
        n += 1
    return out


def convs(cfg):
    """``(name, k, c_in, c_out)`` for every conv node."""
    out = [("conv1", cfg["conv1_kernel"], cfg["in_channels"],
            cfg["conv1_channels"])]
    c_in = cfg["conv1_channels"]
    for item in layers(cfg):
        if item[0] == "fire":
            _, name, c_in, s, e1, e3 = item
            out += [(f"{name}s", 1, c_in, s), (f"{name}e1", 1, s, e1),
                    (f"{name}e3", 3, s, e3)]
            c_in = e1 + e3
    out.append(("conv10", 1, c_in, cfg["num_classes"]))
    return out


def build(b, cfg) -> None:
    y = b.conv("conv1", "input", cfg["conv1_kernel"], cfg["conv1_channels"],
               stride=cfg["conv1_stride"], padding=cfg["conv1_padding"])
    pw, ps = cfg["pool"]["window"], cfg["pool"]["stride"]
    n_pool = 0
    for item in layers(cfg):
        if item[0] == "pool":
            n_pool += 1
            y = b.pool(f"pool{n_pool}", y, kind="max", window=pw, stride=ps,
                       padding=item[1])
            continue
        _, name, _, s, e1, e3 = item
        z = b.conv(f"{name}s", y, 1, s)
        a = b.conv(f"{name}e1", z, 1, e1)
        c = b.conv(f"{name}e3", z, 3, e3)
        y = b.concat(f"{name}cat", (a, c))
    y = b.conv("conv10", y, 1, cfg["num_classes"])
    b.gap("gap", y)


def init(key, cfg):
    """He-normal convs, nonzero biases so the bias path is checked."""
    layers_ = convs(cfg)
    keys = jax.random.split(key, 2 * len(layers_))
    params = {}
    for i, (name, k, c_in, c_out) in enumerate(layers_):
        std = math.sqrt(2.0 / (k * k * c_in))
        params[name] = {
            "w": std * jax.random.normal(keys[2 * i], (k, k, c_in, c_out),
                                         jnp.float32),
            "b": 0.05 * jax.random.normal(keys[2 * i + 1], (c_out,),
                                          jnp.float32)}
    return params


def _conv_relu(x, p, stride, pad):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return jax.nn.relu(y + p["b"])


def reference(params, x, cfg):
    """Class scores ``(N, num_classes)`` of NHWC images ``x``."""
    y = _conv_relu(x, params["conv1"], cfg["conv1_stride"],
                   cfg["conv1_padding"])
    pw, ps = cfg["pool"]["window"], cfg["pool"]["stride"]
    for item in layers(cfg):
        if item[0] == "pool":
            p = item[1]
            y = lax.reduce_window(y, -jnp.inf, lax.max, (1, pw, pw, 1),
                                  (1, ps, ps, 1),
                                  ((0, 0), (p, p), (p, p), (0, 0)))
            continue
        name = item[1]
        z = _conv_relu(y, params[f"{name}s"], 1, 0)
        y = jnp.concatenate([_conv_relu(z, params[f"{name}e1"], 1, 0),
                             _conv_relu(z, params[f"{name}e3"], 1, 1)],
                            axis=-1)
    y = _conv_relu(y, params["conv10"], 1, 0)
    return y.mean(axis=(1, 2))
