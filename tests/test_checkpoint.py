"""Fault-tolerance tests: atomicity, integrity, resume, elastic re-mesh,
gradient compression."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt


def _tree(rng):
    return {"a": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
            "nested": {"b": jnp.asarray(rng.integers(0, 9, (3,)), jnp.int32),
                       "c": jnp.asarray(rng.normal(size=(5,)),
                                        jnp.float32).astype(jnp.bfloat16)}}


def test_roundtrip(tmp_path, rng):
    t = _tree(rng)
    ckpt.save_checkpoint(tmp_path, 7, t)
    like = jax.eval_shape(lambda: t)
    r = ckpt.restore_checkpoint(tmp_path, 7, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_latest_and_gc(tmp_path, rng):
    t = _tree(rng)
    for s in (1, 2, 3, 4, 5):
        ckpt.save_checkpoint(tmp_path, s, t, keep=3)
    assert ckpt.latest_step(tmp_path) == 5
    assert ckpt.latest_steps(tmp_path) == [3, 4, 5]     # older GC'd


def test_corruption_detected(tmp_path, rng):
    t = _tree(rng)
    d = ckpt.save_checkpoint(tmp_path, 1, t)
    manifest = json.loads((d / "manifest.json").read_text())
    fname = manifest["arrays"]["a"]["file"]
    arr = np.load(d / fname)
    arr[0, 0] += 1.0                                   # silent bit-flip
    np.save(d / fname, arr)
    like = jax.eval_shape(lambda: t)
    with pytest.raises(IOError, match="corruption"):
        ckpt.restore_checkpoint(tmp_path, 1, like)


def test_incomplete_checkpoint_ignored(tmp_path, rng):
    """A crash mid-write (tmp dir, no manifest) must be invisible."""
    t = _tree(rng)
    ckpt.save_checkpoint(tmp_path, 3, t)
    (tmp_path / "step_9.tmp").mkdir()                  # simulated crash
    (tmp_path / "step_11").mkdir()                     # no manifest
    assert ckpt.latest_step(tmp_path) == 3


def test_async_checkpoint(tmp_path, rng):
    t = _tree(rng)
    th = ckpt.save_checkpoint(tmp_path, 2, t, async_=True)
    th.join()
    assert ckpt.latest_step(tmp_path) == 2


def test_trainer_resume(tmp_path, rng):
    """Kill-and-restart: the second trainer must resume, not restart."""
    from repro.configs.base import get_config, smoke_variant
    from repro.data import SyntheticLMData
    from repro.train.trainer import Trainer, TrainConfig
    import dataclasses

    cfg = dataclasses.replace(smoke_variant(get_config("qwen2-1.5b")),
                              grad_accum=1)
    data = SyntheticLMData(cfg.vocab_size, 4, 16)
    tcfg = TrainConfig(steps=4, ckpt_every=2, ckpt_dir=str(tmp_path),
                       ckpt_async=False, log_every=100)
    t1 = Trainer(cfg, tcfg, data)
    t1.run()
    assert ckpt.latest_step(tmp_path) == 4

    tcfg2 = TrainConfig(steps=6, ckpt_every=2, ckpt_dir=str(tmp_path),
                        ckpt_async=False, log_every=100)
    t2 = Trainer(cfg, tcfg2, data)
    start = t2.resume_or_init()
    assert start == 4                                   # resumed, not 0
    t2.state = None
    t2.run()
    assert ckpt.latest_step(tmp_path) == 6


def test_elastic_remesh_restore(tmp_path):
    """Save on mesh (4,2), restore onto mesh (2,2) with different device
    count — runs in a subprocess with 8 forced host devices."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt

meshA = jax.make_mesh((4, 2), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xs = jax.device_put(x, NamedSharding(meshA, P("data", "model")))
ckpt.save_checkpoint(r"{tmp_path}", 1, {{"w": xs}})

meshB = jax.make_mesh((2, 2), ("data", "model"))
like = jax.eval_shape(lambda: {{"w": x}})
shard = {{"w": NamedSharding(meshB, P("model", "data"))}}
r = ckpt.restore_checkpoint(r"{tmp_path}", 1, like, shardings=shard)
np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(x))
assert r["w"].sharding.mesh.shape["data"] == 2
print("ELASTIC_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], cwd=Path.cwd(),
                         env=env, capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# gradient compression

def test_quantize_roundtrip_error_bounded(rng):
    from repro.dist import compress as C
    x = jnp.asarray(rng.normal(size=(1000,)) * 3, jnp.float32)
    q, scale, shape = C.quantize(x)
    deq = C.dequantize(q, scale, shape)
    # int8 symmetric: per-block error <= scale/2 = max|block|/254
    err = np.abs(np.asarray(deq - x))
    assert err.max() <= float(jnp.abs(x).max()) / 254 + 1e-6


def test_error_feedback_converges(rng):
    """Sum of EF-compressed gradients converges to the true sum: the
    residual never leaks, it is re-applied next step."""
    from repro.dist import compress as C
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32) * 0.01
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for _ in range(50):
        (q, s, sh), err = C.quantize_with_feedback(g, err)
        total = total + C.dequantize(q, s, sh)
    drift = np.abs(np.asarray(total - 50 * g)).max()
    # residual is bounded by one quantization step, not 50
    assert drift <= float(jnp.abs(g).max()) / 100


def test_compressed_psum_matches_psum(tmp_path):
    """shard_map int8 psum over a 4-device axis ~= exact psum."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.dist import compress as C

mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)), jnp.float32)
err0 = jnp.zeros((4, 64), jnp.float32)

@partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
         out_specs=(P("pod"), P("pod")))
def f(xs, es):
    out, new_e = C.compressed_psum(xs[0], "pod", es[0])
    return out[None], new_e[None]

got, _ = f(x, err0)
want = x.sum(0)
rel = np.abs(np.asarray(got[0] - want)).max() / np.abs(np.asarray(want)).max()
assert rel < 0.02, rel
print("PSUM_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], cwd=Path.cwd(),
                         env=env, capture_output=True, text=True, timeout=300)
    assert "PSUM_OK" in out.stdout, out.stderr[-2000:]
