"""`chip_smoke.py`'s checks, exercised on the CPU at smoke size.

The script itself runs only on a TPU; here it must refuse to start, and the
checks it makes on the chip must pass on correct inputs and fail on wrong
ones.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, smoke_variant
from repro.models.layers import attach_spiking_ffn_plans
from repro.models.registry import build_model
from repro.serve import Engine, ExecutionPolicy, Placement
from repro.serve.sharding import make_serve_mesh

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small(chip_smoke):
    cfg = chip_smoke.smoke_config(smoke_variant(get_config("llama3_2_1b")))
    model, params, prompts = chip_smoke.build(cfg, 0, 4, 8)
    return cfg, model, params, prompts


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["one", "four"])
def test_refuses_to_run_without_a_tpu(chip_smoke, argv):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(argv)
    assert "no TPU" in str(e.value.code)


def test_full_sum_drift_within_the_summation_bound(chip_smoke, small):
    cfg, _, params, _ = small
    drift, tol = chip_smoke.full_sum_drift(
        attach_spiking_ffn_plans(params, cfg), cfg, seed=0, rows=24
    )
    assert 0.0 < tol < 1e-3
    assert drift <= tol


def test_plan_placement_check_on_a_2x2_mesh(chip_smoke, small):
    cfg, model, params, _ = small
    mesh = make_serve_mesh("data=2,model=2", devices=jax.devices()[:4])
    engine = Engine(
        model, params, max_len=16, max_slots=4,
        policy=ExecutionPolicy.for_arch(cfg, placement=Placement(mesh=mesh)),
    )
    chip_smoke.check_plan_placement(engine.params, mesh)

    # a payload replicated on every device holds every slab everywhere
    mlp = engine.params["layers"]["mlp"]
    plan = mlp["plan_in"]
    replicated = type(plan)(
        jax.device_put(plan.payload, NamedSharding(mesh, P())),
        plan.kidx, plan.vidx, plan.cnt, plan.bmap,
    )
    bad = dict(engine.params,
               layers=dict(engine.params["layers"],
                           mlp=dict(mlp, plan_in=replicated)))
    with pytest.raises(chip_smoke.SmokeFailure, match="plan_in slab"):
        chip_smoke.check_plan_placement(bad, mesh)


def test_prompts_and_params_follow_the_seed(chip_smoke, small):
    cfg, _, params, prompts = small
    _, params2, prompts2 = chip_smoke.build(cfg, 0, 4, 8)
    assert all(np.array_equal(a, b) for a, b in zip(prompts, prompts2))
    np.testing.assert_array_equal(
        np.asarray(params["embed"]), np.asarray(params2["embed"])
    )
    assert [len(p) for p in prompts] == [8] * 4


def test_engine_logits_equal_the_step_program_loop(chip_smoke, small):
    cfg, model, params, prompts = small
    gen = 4
    max_len = len(prompts[0]) + gen
    engine = Engine(
        model, params, max_len=max_len, max_slots=4,
        policy=ExecutionPolicy.for_arch(cfg), capture_logits=True,
    )
    outs = engine.generate_batch(prompts, gen)
    with chip_smoke.packed_inference():
        logits, seconds = chip_smoke.greedy_step_logits(
            jax.jit(model.prefill), jax.jit(model.decode), engine.params,
            jnp.asarray(np.stack(prompts)), model.init_cache(4, max_len), gen,
        )
    assert logits.shape == (4, gen, cfg.vocab) and len(seconds) == 2
    np.testing.assert_array_equal(logits.argmax(-1), np.stack(outs))
    traces = engine.drain_logit_traces()
    np.testing.assert_array_equal(np.stack([np.stack(t) for t in traces]), logits)


def test_top2_margin_is_the_smallest_gap_of_any_row(chip_smoke):
    logits = np.array([[0.0, 3.0, 1.0], [5.0, 4.5, -1.0]], np.float32)
    assert chip_smoke.top2_margin(logits) == 0.5
    assert chip_smoke.top2_margin(logits[None]) == 0.5
