"""Sharding-rule unit tests + a real 8-device SPMD train step (subprocess)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_config, smoke_variant
from repro.dist import sharding as sh
from repro.models import lm


def test_every_param_has_a_rule():
    """logical_axes must cover every leaf of every architecture."""
    from repro.configs.base import list_archs
    for arch in list_archs():
        cfg = smoke_variant(get_config(arch))
        shapes = jax.eval_shape(lambda c=cfg: lm.init_lm(
            c, jax.random.PRNGKey(0)))
        axes = sh.logical_axes(shapes)          # raises if any path unmatched
        n_leaves = len(jax.tree.leaves(shapes))
        n_axes = len(jax.tree.leaves(
            axes, is_leaf=lambda a: isinstance(a, tuple)))
        assert n_leaves == n_axes, arch


def test_no_dead_rules():
    """Every _AXIS_TABLE pattern is the FIRST match for at least one
    real param path across the current architectures.  First-match-wins
    means a rule shadowed by an earlier one (or matching a param no
    arch produces anymore) is dead code — this is the test that forces
    pruning it when a param tree changes."""
    from repro.configs.base import list_archs
    first_matches = set()
    for arch in list_archs():
        cfg = smoke_variant(get_config(arch))
        shapes = jax.eval_shape(lambda c=cfg: lm.init_lm(
            c, jax.random.PRNGKey(0)))
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
        for path, _leaf in flat:
            p = sh._path_str(path)
            for i, (pat, _ax) in enumerate(sh._AXIS_TABLE):
                if pat.search(p):
                    first_matches.add(i)
                    break
    dead = [sh._AXIS_TABLE[i][0].pattern
            for i in range(len(sh._AXIS_TABLE)) if i not in first_matches]
    assert not dead, f"dead sharding rules (no param path hits them): {dead}"


def test_param_specs_2d_sharded():
    """Big matrices get both an FSDP ('data') and a TP ('model') axis."""
    cfg = smoke_variant(get_config("qwen2-72b"))
    shapes = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))
    rules = sh.make_rules("train", multi_pod=False)
    specs = sh.param_specs(shapes, rules)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): s
            for path, s in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda s: isinstance(s, sh.P))}
    wq = [v for k, v in flat.items() if k.endswith("attn/wq/w")][0]
    assert wq == sh.P(None, "data", "model")
    emb = flat["embed/embedding"]
    assert emb == sh.P("model", "data")
    mlp_wo = [v for k, v in flat.items() if k.endswith("mlp/wo/w")][0]
    assert mlp_wo == sh.P(None, "model", "data")


def test_multipod_batch_rule():
    r1 = sh.make_rules("train", multi_pod=False)
    r2 = sh.make_rules("train", multi_pod=True)
    assert r1["batch"] == ("data",)
    assert r2["batch"] == ("pod", "data")
    rl = sh.make_rules("decode", multi_pod=False, long_context=True)
    assert rl["batch"] is None and rl["kv_len"] == ("data",)


def test_moe_expert_sharding():
    cfg = smoke_variant(get_config("deepseek-moe-16b"))
    shapes = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))
    rules = sh.make_rules("train", multi_pod=False)
    specs = sh.param_specs(shapes, rules)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): s
            for path, s in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda s: isinstance(s, sh.P))}
    wi = [v for k, v in flat.items() if k.endswith("moe/experts/wi")][0]
    assert wi == sh.P(None, "model", "data", None)    # EP x FSDP


def test_real_spmd_train_step_8dev():
    """End-to-end: 8 forced host devices, (4 data x 2 model) mesh, real
    sharded train step executes and loss is finite."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, smoke_variant
from repro.dist import sharding as sh
from repro.launch import steps as St
from repro.models import lm
from repro.optim import adamw_init

cfg = dataclasses.replace(smoke_variant(get_config("qwen2-1.5b")),
                          d_model=64, num_heads=4, num_kv_heads=2,
                          grad_accum=2)
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(8, model=2)
rules = sh.make_rules("train", multi_pod=False)
state_shapes = St.state_specs(cfg)
pspecs = sh.param_specs(state_shapes["params"], rules)
sspecs = {"params": pspecs, "opt": sh.opt_specs(pspecs), "step": sh.P()}
from jax.sharding import NamedSharding
act = NamedSharding(mesh, sh.P(rules["batch"], None, None))
step = jax.jit(St.make_train_step(cfg, act_spec=act, moe_groups=4,
                                  peak_lr=1e-2),
               in_shardings=(sh.named(mesh, sspecs), None),
               out_shardings=(sh.named(mesh, sspecs), None),
               donate_argnums=(0,))
params = lm.init_lm(cfg, jax.random.PRNGKey(0))
state = {"params": params, "opt": adamw_init(params),
         "step": jnp.zeros((), jnp.int32)}
state = jax.device_put(state, sh.named(mesh, sspecs))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 256, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, 256, (8, 16)), jnp.int32)}
l0 = None
for i in range(3):
    state, m = step(state, batch)
    assert np.isfinite(m["loss"])
    l0 = l0 or float(m["loss"])
assert float(m["loss"]) < l0    # memorizing one batch
print("SPMD_OK", float(m["loss"]))
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], cwd=Path.cwd(),
                         env=env, capture_output=True, text=True,
                         timeout=560)
    assert "SPMD_OK" in out.stdout, out.stderr[-3000:]


def test_serve_helpers_replicate_once_and_batch_shard():
    """The data-parallel serving helpers the sharded dispatcher is
    built on: replicate_params moves a host tree exactly once (already
    replicated leaves pass through by identity), batch_sharded cuts
    only the leading axis."""
    import numpy as np

    from repro.launch.mesh import SERVE_AXIS, make_serve_mesh

    mesh = make_serve_mesh()
    params = {"w": np.ones((4, 3), np.float32),
              "inner": {"b": np.zeros((3,), np.float32)}}
    rep = sh.replicate_params(params, mesh)
    leaves = jax.tree.leaves(rep)
    assert all(sh.is_replicated_on(leaf, mesh) for leaf in leaves)
    assert not sh.is_replicated_on(params["w"], mesh)   # host array isn't
    # second replication is the identity — no re-transfer
    rep2 = sh.replicate_params(rep, mesh)
    assert all(a is b for a, b in zip(leaves, jax.tree.leaves(rep2)))

    assert sh.replicated(mesh).spec == sh.P()
    assert sh.batch_sharded(mesh, 4).spec == sh.P(
        SERVE_AXIS, None, None, None)
    assert sh.batch_sharded(mesh, 1).spec == sh.P(SERVE_AXIS)
    with pytest.raises(ValueError, match="rank"):
        sh.batch_sharded(mesh, 0)
    with pytest.raises(ValueError, match="n_devices"):
        make_serve_mesh(len(jax.local_devices()) + 1)


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %all-gather.46 = f32[16,4096,1,128]{2,1,0,3} all-gather(%x), dims={3}
  %fusion.1 = f32[4,4]{1,0} fusion(%all-reduce.189), calls=%c
  %all-reduce.189 = f32[256,4096]{1,0} all-reduce(%w), channel_id=1
  %all-to-all.40 = (f32[1,32,8]{2,1,0}, f32[1,32,8]{2,1,0}) all-to-all(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"]["bytes"] == 16 * 4096 * 128 * 4
    assert out["all-reduce"]["bytes"] == 256 * 4096 * 4
    assert out["all-to-all"]["bytes"] == 2 * 32 * 8 * 4
    assert out["all-gather"]["count"] == 1
