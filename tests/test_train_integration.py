"""End-to-end integration: training loop learns, checkpoints restart
bit-exactly, grad compression trains, spiking-FFN LM trains, and the
multi-device sharded lowering works (subprocess with fake devices)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_config, smoke_variant
from repro.data.pipeline import SyntheticLMData
from repro.models.registry import build_model
from repro.optim import get_optimizer
from repro.optim.schedules import constant
from repro.train.step import init_train_state, make_train_step


def _setup(arch="llama3_2_1b", **overrides):
    cfg = smoke_variant(get_config(arch))
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128, **overrides)
    model = build_model(cfg)
    data = SyntheticLMData(cfg, seq_len=32, global_batch=4)
    return cfg, model, data


def _smoke_optimizer(cfg, lr=3e-3):
    """Constant-lr optimizer for the <=30-step integration budget.

    The production default (`warmup_cosine(3e-4, 200, 10000)`) never leaves
    warmup inside these tests — lr peaks at 15 % of an already-small 3e-4,
    and the loss just oscillates around its starting value.
    """
    return get_optimizer(cfg.optimizer, constant(lr))


def _run(model, data, state, steps, start=0, optimizer=None):
    step_fn = jax.jit(make_train_step(model, optimizer=optimizer))
    losses = []
    for s in range(start, start + steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


def test_training_learns():
    cfg, model, data = _setup()
    opt = _smoke_optimizer(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0), optimizer=opt)
    state, losses = _run(model, data, state, 30, optimizer=opt)
    assert losses[-1] < losses[0] - 0.2, losses[:: max(len(losses) // 5, 1)]
    assert np.isfinite(losses).all()


def test_checkpoint_restart_is_bit_exact(tmp_path):
    cfg, model, data = _setup()
    state = init_train_state(model, jax.random.PRNGKey(0))

    # run A: 10 straight steps
    state_a, _ = _run(model, data, state, 10)

    # run B: 5 steps, checkpoint, restore into fresh state, 5 more
    state_b, _ = _run(model, data, state, 5)
    mgr = CheckpointManager(str(tmp_path), interval=1, async_save=False)
    mgr.maybe_save(5, state_b, force=True)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state_b)
    restored, step = mgr.restore_latest(like)
    assert step == 5
    state_b2, _ = _run(model, data, restored, 5, start=5)

    for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_compression_trains():
    cfg, model, data = _setup()
    state = init_train_state(model, jax.random.PRNGKey(0), grad_compress=True)
    step_fn = jax.jit(make_train_step(model, grad_compress=True))
    losses = []
    for s in range(20):
        batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_spiking_ffn_lm_trains():
    cfg, model, data = _setup(spiking_ffn=True, spiking_T=4,
                              spiking_weight_density=0.3)
    opt = _smoke_optimizer(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0), optimizer=opt)
    state, losses = _run(model, data, state, 25, optimizer=opt)
    assert losses[-1] < losses[0] - 0.1, losses


def test_adafactor_arch_trains():
    cfg, model, data = _setup("phi3_5_moe")
    assert cfg.optimizer == "adafactor"
    opt = _smoke_optimizer(cfg, lr=1e-2)
    state = init_train_state(model, jax.random.PRNGKey(0), optimizer=opt)
    state, losses = _run(model, data, state, 20, optimizer=opt)
    assert losses[-1] < losses[0]


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config, smoke_variant
from repro.data.pipeline import SyntheticLMData
from repro.models import transformer
from repro.models import layers as model_layers
from repro.models.registry import build_model
from repro.sharding import base_rules, batch_specs, make_shard_hook, make_qkv_hook, tree_shardings
from repro.train.step import init_train_state, make_train_step, train_state_axes
from repro.ft.elastic import plan_mesh, reshard_state

cfg = smoke_variant(get_config("llama3_2_1b"))
cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128, n_heads=4, n_kv=2)
mesh = plan_mesh(8, model_parallel=2)
rules = base_rules()
transformer.set_shard_hook(make_shard_hook(mesh, rules))
model_layers.set_qkv_hook(make_qkv_hook(mesh, rules))
model = build_model(cfg)
data = SyntheticLMData(cfg, seq_len=32, global_batch=8)
state = init_train_state(model, jax.random.PRNGKey(0))
axes = train_state_axes(model)
shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
sh = tree_shardings(shapes, axes, mesh, rules)
state = jax.tree.map(lambda a, s: jax.device_put(a, s), state, sh)
step = jax.jit(make_train_step(model), donate_argnums=(0,))
with mesh:
    for i in range(4):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        state, m = step(state, batch)
l8 = float(m["loss"])
assert np.isfinite(l8)

# elastic re-scale: 8 -> 4 devices, reshard, keep stepping
host = jax.tree.map(lambda a: np.asarray(a), state)
mesh4 = plan_mesh(4, model_parallel=2)
transformer.set_shard_hook(make_shard_hook(mesh4, rules))
model_layers.set_qkv_hook(make_qkv_hook(mesh4, rules))
state4 = reshard_state(host, axes, mesh4, rules)
step4 = jax.jit(make_train_step(model), donate_argnums=(0,))
with mesh4:
    batch = {k: jnp.asarray(v) for k, v in data.batch(4).items()}
    state4, m4 = step4(state4, batch)
assert np.isfinite(float(m4["loss"]))
print("MULTIDEV_OK", l8, float(m4["loss"]))
"""


def test_multidevice_sharded_training_and_elastic_rescale():
    """Real 8-fake-device run: sharded train steps + elastic 8->4 reshard.
    Subprocess because the device count is locked at first jax init."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT], env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=500,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIDEV_OK" in out.stdout, out.stdout


def test_compressed_psum_shardmap():
    """int8-EF compressed all-reduce building block under shard_map
    (subprocess, 4 fake devices)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compressed_psum
mesh = jax.make_mesh((4,), ("data",))
x = jnp.arange(64, dtype=jnp.float32).reshape(4, 16) / 7.0
f = jax.shard_map(lambda g: compressed_psum(g[0], "data")[None],
                  mesh=mesh, in_specs=P("data", None),
                  out_specs=P("data", None), check_vma=False)
got = np.asarray(f(x))
want = np.asarray(x.mean(0))
assert np.allclose(got[0], want, atol=np.abs(want).max()/100), (got[0], want)
print("PSUM_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PSUM_OK" in out.stdout
