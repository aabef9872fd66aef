"""The spiking-expert decoder's reference and check, at CPU size.

* A whole run of the harness on a small expert model (the engine's packed
  path with the grouped BSR kernel in the Pallas interpreter, the open
  loop, the check) comes out correct, and every broken reference the cell
  is meant to catch comes out not correct: the float8 control, each
  token's 8th expert dropped (`top7`, here the last of two), capacity
  routing at factor 1.25, sliding layers attending fully, the experts'
  output lost.
* The same run with the program's routing broken underneath (each token
  sent to the expert after the one it chose) comes out not correct.
* The new configuration, traffic, cell and metrics are found by name and
  keep `BENCHMARK.json`'s form.

The small model computes in float32 and keeps a float32 KV cache: the
program then fires the same spikes as the reference (its logit gaps read
0), so the limits sit just above zero and a fault that moves a single
served token's logit past the reference's best fails the worst request.
With bfloat16 anywhere, the program's own spike flips at these widths are
as large as the faults confined to two small experts.
"""
import copy
import json
import time

import jax
import numpy as np
import pytest

import conftest
import run
import spec

CONF = json.loads((conftest.BENCH / "configs" / "mellum2-12b-a2.5b-spk.json")
                  .read_text())
SMALL = dict(hidden_size=256, num_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, vocab_size=512,
             sliding_window=16)
MIX = {"name": "small_code_completion", "arrivals": {"process": "poisson"},
       "prompt_len": {"values": [24, 40], "weights": [0.5, 0.5]},
       "output_len": {"dist": "uniform", "min": 8, "max": 16},
       "tokens": {"dist": "uniform"}}
LIMITS = {"mean_logit_gap": 1e-4, "worst_request_gap": 4e-4}


@pytest.fixture
def moe_cell(monkeypatch):
    import jax.numpy as jnp

    from repro.models import transformer

    named = run.named_steps

    def f32_cache(model):
        cfg = model.cfg
        return run.replace(named(model), init_cache=lambda b, s: (
            transformer.init_cache(cfg, b, s, dtype=jnp.float32)))

    monkeypatch.setattr(run, "named_steps", f32_cache)
    conf = copy.deepcopy(CONF)
    conf.update(SMALL)
    conf["spiking"]["weight_density"] = 0.5
    conf["dtypes"]["compute"] = "float32"
    conf["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=32, factor=4)
    geo = {"rate_rps": 4.0, "max_slots": 4, "batch_align": 1,
           "sample_requests": 6, "limits": dict(LIMITS)}
    return spec.Cell(name="small.moe", chips=1, conf=conf, mix=dict(MIX),
                     geometry=geo, end_to_end=[], per_layer=[])


def _run(cell, seed, **kw):
    return run.run_cell(cell, seed=seed, seconds=2.0, trace=False,
                        t_start=time.perf_counter(), devs=jax.devices(),
                        peaks=conftest.CPU_PEAKS, **kw)


@pytest.mark.parametrize("seed", [1, 2])
def test_program_passes_and_every_broken_reference_fails(moe_cell, seed):
    r = _run(moe_cell, seed, control=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["window"]["compiles"] == 0
    assert set(r["controls"]) == set(moe_cell.reference().VARIANTS)
    for name, c in r["controls"].items():
        assert c["correct"] is False, (name, c["gaps"])


def test_routing_to_the_wrong_expert_fails(moe_cell, monkeypatch):
    from repro.models import layers

    route = layers._route

    def swapped(router, xt, n_experts, k):
        gate, eidx, aux = route(router, xt, n_experts, k)
        return gate, (eidx + 1) % n_experts, aux

    monkeypatch.setattr(layers, "_route", swapped)
    r = _run(moe_cell, 3)
    assert not r["correct"]
    assert r["checks"]["unfinished_requests"]["value"] == 0


def test_the_new_cell_is_found_by_name_and_keeps_the_form():
    import test_harness

    doc = json.loads((conftest.ROOT / "BENCHMARK.json").read_text())
    assert "mellum2-12b-a2.5b-spk" in [c["name"] for c in doc["configs"]]
    cell = spec.Spec(conftest.ROOT).cell("mellum2.code_completion")
    assert cell.conf["model"] == "spiking_moe_decoder"
    assert cell.mix["name"] == "code_completion"
    assert {m["name"] for m in cell.end_to_end} == {
        "itl_p99_ms", "out_tok_s", "setup_s"}
    assert {"expert_bsr_roofline.prefill", "mfu.moe_prefill"} <= {
        m["name"] for m in cell.per_layer}
    assert cell.adapter().layer_types(cell.conf) == (
        "sliding_attention",) * 3 + ("full_attention",)
    old = spec.Spec(conftest.ROOT).cell("mistral7b.prefill_heavy")
    assert "mfu.moe_prefill" not in [m["name"] for m in old.per_layer]
    test_harness.test_benchmark_json_keeps_its_form()
    test_harness.test_metric_files_agree_with_benchmark_json()


def test_expert_counts_by_hand():
    import counts
    import counts_moe

    conf = copy.deepcopy(CONF)
    assert counts_moe.expert_conf(counts.Shapes.of(conf))["name"] == conf["name"]
    # 8 experts x 2 GEMMs of 2 * 4 * 0.3 * 2304 * 896, and the router
    per_tok = counts_moe.expert_flops(conf)
    assert per_tok == 8 * 2 * (2 * 4 * 0.3 * 2304 * 896) + 2 * 2304 * 64
    assert counts_moe.attended(3, 2) == 1 + 2 + 2
    assert counts_moe.attended(3, None) == 6
    s = counts.Shapes.of(conf)
    P = 2048
    attn = 4.0 * 32 * 128 * (3 * counts_moe.attended(P, 1024)
                             + counts_moe.attended(P, None))
    want = 4 * P * (counts.proj_flops(s) + per_tok) + attn + 2 * 2304 * 98304
    assert counts_moe.prefill_flops(conf, P, 1) == pytest.approx(want)
    f, b = counts_moe.grouped_bsr_call(conf, 1, "down")
    assert f == 2 * 4 * 0.3 * 8 * 896 * 2304
    assert b == 8 * 0.3 * 896 * 2304 * 2 + 8 * 896 * 4 + 8 * 2304 * 4
    _, b = counts_moe.grouped_bsr_call(conf, 1000, "up")
    assert b == 64 * 0.3 * 2304 * 896 * 2 + 8000 * 2304 * 4 + 8000 * 896 * 4


def test_moe_metrics_read_nothing_without_their_model():
    import types

    import counts

    dense = counts.Shapes.of(json.loads(
        (conftest.BENCH / "configs" / "mistral-7b-spk.json").read_text()))
    rec = types.SimpleNamespace(shapes=dense, trace=None, dispatches=[],
                                window=types.SimpleNamespace(trace_span=None))
    for name in ("expert_bsr_roofline.prefill", "mfu.moe_prefill"):
        assert spec.metric_module(name).compute(rec) is None
