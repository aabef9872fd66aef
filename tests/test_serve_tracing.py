"""The serving path's own profiler spans and named scopes.

A smoke-size packed dual-sparse engine runs under `jax.profiler` on the
CPU; the ``.xplane.pb`` it writes is read back with `ProfileData`:

* each `Engine.step()` is one ``serve.step`` span holding one
  ``serve.decode``, ``serve.sample_sync`` and ``serve.encode`` per cohort
  decode, in that order, with the host's waits as child spans;
* the decode spans count the engine's decode dispatches, and the prefill
  spans name the requests they admitted;
* the compiled decode program tags its dots with the model's named scopes
  (``attention``, ``ffn.up``, ``ffn.down``, ``head``) in ``op_name``.
"""
import dataclasses
import glob
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import layers as model_layers
from repro.models.registry import build_model
from repro.serve import Engine, ExecutionPolicy

STAGE_KEYS = {"admit", "ingest", "merge", "prefill", "retire", "decode",
              "sample_sync", "encode"}


@pytest.fixture(scope="module")
def smoke():
    cfg = smoke_variant(get_config("llama3_2_1b"))
    cfg = dataclasses.replace(cfg, spiking_ffn=True, spiking_T=4,
                              spiking_weight_density=0.3)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, n, length=12, seed=0):
    rng = np.random.default_rng(seed)
    return [np.asarray(rng.integers(0, cfg.vocab, size=(length,)), np.int32)
            for _ in range(n)]


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict

    def holds(self, other) -> bool:
        return self.start <= other.start and other.end <= self.end


def _serve_spans(trace_dir) -> list[Span]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    out = []
    # jaxlib's stats type warns that it has no __module__ (a DeprecationWarning
    # raised inside its iterator aborts the process under -W error)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.append(Span(e.name, e.start_ns, e.end_ns,
                                        dict(e.stats)))
    return sorted(out, key=lambda s: (s.start, -s.end))


@pytest.fixture(scope="module")
def traced(smoke, tmp_path_factory):
    """Two requests admitted together, a third one step later (so one step
    decodes two cohorts), traced from the first submit to idle."""
    cfg, model, params = smoke
    prompts = _prompts(cfg, 3)
    try:
        engine = Engine(model, params, max_len=24, max_slots=4,
                        policy=ExecutionPolicy.for_arch(cfg))
        assert engine.spiking_packed and engine.spiking_dual_sparse
        engine.generate_batch(prompts, 3)       # compile outside the trace
        keys0 = set(engine.metrics.stage_s)
        n0 = engine.metrics.n_decode_batches
        d = tmp_path_factory.mktemp("trace")
        jax.profiler.start_trace(str(d))
        try:
            first = [engine.submit(p, 4).rid for p in prompts[:2]]
            engine.step()
            late = engine.submit(prompts[2], 3).rid
            while not engine.idle:
                engine.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        model_layers.set_spiking_ffn_mode("train")
    return {"spans": _serve_spans(d), "engine": engine, "keys0": keys0,
            "decodes": engine.metrics.n_decode_batches - n0,
            "first": first, "late": late}


def test_steps_hold_decode_sample_encode_per_cohort(traced):
    spans = traced["spans"]
    steps = [s for s in spans if s.name == "serve.step"]
    assert steps
    assert [s.args["step_num"] for s in steps] == list(
        range(steps[0].args["step_num"], steps[0].args["step_num"] + len(steps)))
    two_cohorts = 0
    for step in steps:
        inner = [s for s in spans if s is not step and step.holds(s)]
        seq = [s.name for s in inner if s.name in
               ("serve.decode", "serve.sample_sync", "serve.encode")]
        n = seq.count("serve.decode")
        assert seq == ["serve.decode", "serve.sample_sync", "serve.encode"] * n
        two_cohorts += n == 2
        for parent, child in (("serve.sample_sync", "serve.sample_sync.wait"),
                              ("serve.encode", "serve.encode.wait")):
            outer = [s for s in inner if s.name == parent]
            waits = [s for s in inner if s.name == child]
            assert len(waits) >= len(outer)
            assert all(any(o.holds(w) for w in waits) for o in outer)
    assert two_cohorts >= 1
    # every serve span of the traced run lies inside a step
    assert all(any(st.holds(s) for st in steps) for s in spans)


def test_decode_spans_count_dispatches_and_carry_rows(traced):
    decodes = [s for s in traced["spans"] if s.name == "serve.decode"]
    assert len(decodes) == traced["decodes"] > 0
    for s in decodes:
        assert 1 <= s.args["live"] <= s.args["rows"]
        assert s.args["length"] > 12


def test_prefill_and_retire_spans_name_their_requests(traced):
    def rids(s):
        return [int(x) for x in str(s.args["rids"]).split()]

    prefills = [s for s in traced["spans"] if s.name == "serve.prefill"]
    assert [rids(s) for s in prefills] == [traced["first"], [traced["late"]]]
    assert [s.args["rows"] for s in prefills] == [2, 1]
    assert all(s.args["length"] == 12 for s in prefills)
    retired = sorted(r for s in traced["spans"]
                     if s.name == "serve.retire" and "rids" in s.args
                     for r in rids(s))
    assert retired == sorted(traced["first"] + [traced["late"]])


def test_stage_clock_keeps_its_keys(traced):
    engine = traced["engine"]
    assert set(engine.metrics.stage_s) == traced["keys0"] == STAGE_KEYS
    assert all(v > 0.0 for v in engine.metrics.stage_s.values())


def test_pipelined_steps_hold_their_stage_spans(smoke, tmp_path):
    """The pipelined executor's steps, the straggler fold included, run
    inside one ``serve.step`` each; its decodes are spans too."""
    cfg, model, params = smoke
    try:
        engine = Engine(model, params, max_len=24, max_slots=4,
                        policy=ExecutionPolicy.for_arch(
                            cfg, execution="pipelined"))
        prompts = _prompts(cfg, 2, seed=3)
        engine.generate_batch(prompts, 3)
        n0 = engine.metrics.n_decode_batches
        jax.profiler.start_trace(str(tmp_path))
        try:
            for p in prompts:
                engine.submit(p, 4)
            steps = 0
            while not engine.idle:
                engine.step()
                steps += 1
        finally:
            jax.profiler.stop_trace()
    finally:
        model_layers.set_spiking_ffn_mode("train")
    spans = _serve_spans(tmp_path)
    outer = [s for s in spans if s.name == "serve.step"]
    assert len(outer) == steps
    assert all(any(st.holds(s) for st in outer) for s in spans)
    decodes = [s for s in spans if s.name == "serve.decode"]
    assert len(decodes) == engine.metrics.n_decode_batches - n0 > 0
    assert any(s.name == "serve.sample_sync.wait" for s in spans)


def test_span_arguments_are_built_only_while_tracing(tmp_path):
    """With no trace active a stage span is the profiler's enabled check
    alone: its argument callable never runs; under a trace it does."""
    from repro.serve.executor import _StageClock
    from repro.serve.metrics import EngineMetrics

    m, calls = EngineMetrics(), []

    def args():
        calls.append(1)
        return {"rows": 1}

    with _StageClock(m, "decode", args) as clk:
        assert clk.trace is None
    assert calls == [] and m.stage_s["decode"] >= 0.0
    jax.profiler.start_trace(str(tmp_path))
    try:
        with _StageClock(m, "decode", args) as clk:
            assert clk.trace is not None
    finally:
        jax.profiler.stop_trace()
    assert calls == [1]
    assert [s.name for s in _serve_spans(tmp_path)] == ["serve.decode"]


def _dot_scopes(hlo: str) -> set[str]:
    """The op_name metadata of every dot in optimized HLO text."""
    out = set()
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and re.search(r"\bdot\(", line):
            out.add(m.group(1))
    return out


def test_compiled_decode_tags_dots_with_scopes(smoke):
    cfg, model, params = smoke
    try:
        engine = Engine(model, params, max_len=24, max_slots=4,
                        policy=ExecutionPolicy.for_arch(cfg))
        model_layers.set_spiking_ffn_mode("infer")
        hlo = jax.jit(model.decode).lower(
            engine.params, jnp.zeros((1, 1), jnp.int32),
            model.init_cache(1, 24),
        ).compile().as_text()
    finally:
        model_layers.set_spiking_ffn_mode("train")
    names = _dot_scopes(hlo)
    parts = [set(n.split("/")) for n in names]
    for scope in ("attention", "ffn.up", "ffn.down", "head"):
        assert any(scope in p for p in parts), (scope, sorted(names))
    assert all(p & {"attention", "ffn", "head"} for p in parts), sorted(names)
    assert all("ffn" in p for p in parts if p & {"ffn.up", "ffn.down"})
