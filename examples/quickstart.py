"""Quickstart: the cuConv public API in 40 lines.

Plans one convolution (the paper's headline 1x1 layer) as a TPU and as
this machine negotiate it, runs it through every registered executor
that can take it (the Pallas kernels in interpret mode off the TPU) and
checks they agree with the library conv; then uses the cuDNN-style
per-layer autotuner — both the algorithm sweep and the launch-config
sweep (tile geometry per convolution configuration, the paper's own
config-selection lever).

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import conv2d
from repro.core import executors
from repro.core.autotune import measure_algorithm
from repro.core.convspec import ConvSpec, plan

rng = np.random.default_rng(0)
# the paper's headline configuration: 7x7x832 input, 256 1x1 filters,
# batch 1 (GoogleNet inception 5a) — cuConv's 2.29x region on V100
x = jnp.asarray(rng.normal(size=(1, 7, 7, 832)), jnp.float32)
w = jnp.asarray(rng.normal(size=(1, 1, 832, 256)), jnp.float32)
spec = ConvSpec.for_conv(x, w)

for backend in ("tpu", jax.default_backend()):
    name, source, reason = executors.negotiate(spec, backend)
    print(f"negotiated on {backend}: {name} [{source}] {reason}")

ref = conv2d(x, w, algorithm="lax")
out = conv2d(x, w)                  # algorithm="auto": plan() negotiates
print(f"output shape: {out.shape}   {plan(spec).explain()}")
for name in executors.supporting(spec):
    err = float(jnp.abs(conv2d(x, w, algorithm=name) - ref).max())
    print(f"  {name:16s} max_err_vs_library = {err:.2e}")

print(f"measured best on this machine: {measure_algorithm(x, w)}")

# launch-config tuning: sweep the fused kernel's VMEM-feasible tile
# geometries for THIS configuration and persist the (algorithm, config)
# winner — a later plan() replays it from cache with zero re-measurement
tuned = plan(spec, force="cuconv_pallas", tune="full")
print(f"tuned launch config: {tuned.algorithm} "
      f"cfg[{tuned.config_source}]={tuned.config.key()}")
replay = plan(spec, force="cuconv_pallas")
assert replay.config == tuned.config and replay.config_source == "measured"
