"""The reference against the program, at smoke widths on the CPU.

* The reference is the model: at float32 compute, with a float32 cache,
  the program's float path gives the reference's logits to rounding.
* A whole run of the harness (the engine's packed kernel path in the
  Pallas interpreter, the open loop, the check) comes out correct, and
  the float8 control's reading is above the limit that run is held to.
* The same run with the timed path broken underneath comes out not
  correct: a token altered where it is produced, a decode step that
  returns its cache unchanged, half of a decode's rows left out, and the
  spiking FFN's output lost in the BSR path.
"""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import run


def _cell(tiny_cell, **conf):
    cell = copy.deepcopy(tiny_cell)
    cell.conf.update(conf)
    return cell


def _run(cell, seed, **kw):
    return run.run_cell(cell, seed=seed, seconds=2.0, trace=False,
                        t_start=time.perf_counter(), devs=jax.devices(),
                        peaks=conftest.CPU_PEAKS, **kw)


def test_reference_is_the_program_at_float32(tiny_cell):
    from repro.models import transformer
    from repro.models.registry import build_model

    cell = _cell(tiny_cell)
    cell.conf["dtypes"] = dict(cell.conf["dtypes"], compute="float32")
    w = cell.adapter().make_weights(cell.conf, 3)
    cfg = cell.adapter().arch_config(cell.conf)
    model = build_model(cfg)
    # the harness's weights have the program's own layout and shapes
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(w)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(w)))
    toks = np.random.default_rng(0).integers(0, 256, size=24, dtype=np.int32)
    ref = cell.reference()
    for n in (5, 13, 24):
        cache = transformer.init_cache(cfg, 1, 32, dtype=jnp.float32)
        got, _ = model.prefill(w, {"tokens": jnp.asarray(toks[None, :n])},
                               cache)
        lg, _ = ref.served_logits(cell.conf, w, toks[:n], np.zeros(1), 32, 1)
        assert np.abs(np.asarray(got[0, -1]) - lg[0]).max() < 1e-4


# A size a test run holds at which the float8 control separates from
# the program: at d_model 128 or 256 spike flips of the narrow FFN put the
# program as far off as the control.  Readings on the CPU (seeds 1-8,
# PERF.md): mean logit gap, program 0.00173, 0.00197, 0.00434, 0.00466,
# 0.00647, 0.00075, 0.00598, 0.00324; control 0.0203, 0.0204, 0.0253,
# 0.0212, 0.0201, 0.0255, 0.0249, 0.0197.  Worst request's mean gap,
# program 0.0054, 0.0079, 0.0129, 0.0152, 0.0205, 0.0029, 0.0132, 0.0078;
# control 0.0456, 0.0377, 0.0627, 0.0406, 0.0415, 0.0487, 0.0566, 0.0691.
# Each limit lies between,
# nearer the control; at this size the control fails on the mean.
SMALL = dict(hidden_size=512, intermediate_size=4096, vocab_size=2048,
             head_dim=128)
SMALL_LIMIT = 0.014
SMALL_WORST_LIMIT = 0.03


@pytest.fixture
def small_cell(tiny_cell):
    cell = _cell(tiny_cell, **SMALL)
    cell.mix["output_len"] = {"dist": "uniform", "min": 8, "max": 16}
    cell.geometry["sample_requests"] = 8
    cell.geometry["limits"] = {"mean_logit_gap": SMALL_LIMIT,
                               "worst_request_gap": SMALL_WORST_LIMIT}
    return cell


def test_program_passes_and_float8_control_fails(small_cell):
    r = _run(small_cell, 4, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 8 and r["failed"] == 0
    assert r["window"]["compiles"] == 0
    fp8 = r["controls"]["fp8"]
    assert fp8["correct"] is False
    assert fp8["gaps"]["mean_gap"] > SMALL_LIMIT
    assert r["controls"]["ffn_zeroed"]["correct"] is False
    assert list(r)[-1] == "checks"


def test_an_ffn_that_loses_its_output_fails(small_cell, monkeypatch):
    """The BSR path's full sums of the FFN's second GEMM come back zero:
    a fault confined to the spiking FFN."""
    from repro.core import snn_layers

    ffn = snn_layers._ffn_dual_sparse

    def lost(*a, **k):
        packed_h, o = ffn(*a, **k)
        return packed_h, jnp.zeros_like(o)

    monkeypatch.setattr(snn_layers, "_ffn_dual_sparse", lost)
    r = _run(small_cell, 8)
    assert not r["correct"]
    assert r["checks"]["unfinished_requests"]["value"] == 0
    assert r["checks"]["mean_logit_gap"]["value"] > SMALL_LIMIT


def test_an_altered_token_fails(small_cell, monkeypatch):
    from repro.serve import scheduler

    emit = scheduler.RequestState.emit

    def altered(self, tok, eos):
        if len(self.generated) == 2:
            tok = (tok + 1) % SMALL["vocab_size"]
        return emit(self, tok, eos)

    monkeypatch.setattr(scheduler.RequestState, "emit", altered)
    r = _run(small_cell, 5)
    assert not r["correct"]
    assert r["checks"]["mean_logit_gap"]["value"] > SMALL_LIMIT


def test_a_decode_that_keeps_its_cache_fails(small_cell, monkeypatch):
    named = run.named_steps

    def stale(model):
        m = named(model)
        decode = m.decode

        def serve_decode(p, tokens, cache):
            logits, _ = decode(p, tokens, cache)
            return logits, cache

        return run.replace(m, decode=serve_decode)

    monkeypatch.setattr(run, "named_steps", stale)
    r = _run(small_cell, 6)
    assert not r["correct"]
    assert r["checks"]["unfinished_requests"]["value"] == 0
    assert r["checks"]["mean_logit_gap"]["value"] > SMALL_LIMIT


def test_half_the_rows_left_out_fails(small_cell, monkeypatch):
    """Each decode computes the first half of its rows; the rest get the
    first row's logits.  One prompt length, a raised rate and four slots
    make requests queue behind the first prefill and enter as one group,
    so cohorts hold several rows."""
    named = run.named_steps

    def halved(model):
        m = named(model)
        decode = m.decode

        def serve_decode(p, tokens, cache):
            logits, new = decode(p, tokens, cache)
            keep = (tokens.shape[0] + 1) // 2
            logits = logits.at[keep:].set(logits[:1])
            return logits, new

        return run.replace(m, decode=serve_decode)

    monkeypatch.setattr(run, "named_steps", halved)
    small_cell.geometry.update(rate_rps=8.0, max_slots=4)
    small_cell.mix["prompt_len"] = {"values": [8], "weights": [1.0]}
    r = _run(small_cell, 7)
    assert not r["correct"]
    assert r["checks"]["unfinished_requests"]["value"] == 0
    assert r["checks"]["mean_logit_gap"]["value"] > SMALL_LIMIT
