"""The reduction by the serving path's own spans and scopes
(`bench/serve_spans.py`).

* On a hand-built trace, every number is the arithmetic done by hand.
* On the CPU, the scope map of a tiny cell's step programs holds the
  model's scopes, and a program older than its spans reads nothing.
* On each stored trace recorded with its scope map
  (``bench/traces/<cell>.serve_spans.json``): the reduction replays to the
  recorded readings; the three idle shares sum to no more than
  `device_idle_share`; the scopes hold at least 90 % of the decode
  program's self time; and ``programs_per_decode`` is the launches inside
  the decode stages, counted here span by span, per decode program.
"""
import json
from dataclasses import dataclass, field
from pathlib import Path

import pytest

import serve_spans as ss
import trace_reduce

BENCH = Path(__file__).resolve().parents[1]
STORED = sorted((BENCH / "traces").glob("*.serve_spans.json"))


# -- a hand-built trace ------------------------------------------------------

@dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class PD:
    planes: list


LAUNCH = ss.LAUNCH_EVENTS[0]


def _pd():
    # host: one step; a decode stage (launch at 5), its sample_sync with a
    # wait child (launches at 31 and 33), encode (launch at 52), retire
    host = [
        Ev("serve.step", 0, 100),
        Ev("serve.decode", 2, 20), Ev(LAUNCH, 5, 6),
        Ev("serve.sample_sync", 30, 45), Ev("serve.sample_sync.wait", 32, 40),
        Ev(LAUNCH, 31, 32), Ev(LAUNCH, 33, 34),
        Ev("serve.encode", 50, 70), Ev(LAUNCH, 52, 53),
        Ev("serve.retire", 80, 90),
        Ev("bench.step", 0, 100),
    ]
    mods = [Ev("jit_serve_decode(7)", 10, 35), Ev("jit_add(1)", 36, 37),
            Ev("jit__lambda(2)", 60, 62)]
    # ops of the decode program: a while (10..30) holding attention (12..16)
    # and ffn.up (18..26); the head after it (31..35)
    ops = [Ev("%while.3 = (s32[]) while(...)", 10, 30),
           Ev("%fusion.1 = bf16[1] fusion(...)", 12, 16),
           Ev("%_bsr_call.2 = f32[4] custom-call(...)", 18, 26),
           Ev("%fusion.9 = f32[9] fusion(...)", 31, 35),
           Ev("%add.0 = s32[] add(...)", 36, 37),
           Ev("%fusion.0 = u32[1] fusion(...)", 60, 62)]
    return PD([
        Plane("/host:CPU", [Line("python3", host)]),
        Plane("/device:TPU:0", [Line(trace_reduce.MODULE_LINE, mods),
                                Line(trace_reduce.OPS_LINE, ops)]),
    ])


SCOPES = {"jit_serve_decode": {
    "%while.3 = (s32[]) while": "unscoped",
    "%fusion.1 = bf16[1] fusion": "attention",
    "%_bsr_call.2 = f32[4] custom-call": "ffn.up",
    "%fusion.9 = f32[9] fusion": "head"}}


def test_hand_built_trace_reduces_by_hand():
    red = ss.reduce(_pd(), SCOPES)
    # busy: 10..30, 31..35, 36..37, 60..62 -> gaps 30..31 (mid 30.5, in
    # sample_sync), 35..36 (mid 35.5, in its wait: stage sample_sync) and
    # 37..60 (mid 48.5, in no serve span but the step)
    assert red["idle_s"] == {
        ("serve.sample_sync", "serve.sample_sync"): 1e-9,
        ("serve.sample_sync.wait", "serve.sample_sync"): 1e-9,
        ("serve.step", "serve.step"): 23e-9}
    assert red["launches"] == {"serve.decode": 1, "serve.sample_sync": 2,
                               "serve.encode": 1}
    assert red["calls"] == {"decode": 1}
    assert red["self_s"][("decode", "unscoped")] == pytest.approx(8e-9)
    assert red["self_s"][("decode", "attention")] == pytest.approx(4e-9)
    assert red["self_s"][("decode", "ffn.up")] == pytest.approx(8e-9)
    assert red["self_s"][("decode", "head")] == pytest.approx(4e-9)
    got = ss.readings(red, 100e-9, SCOPES)
    assert got["idle_share.dispatch"] == 0.0
    assert got["idle_share.sample"] == pytest.approx(2.0)
    assert got["idle_share.encode"] == 0.0
    assert got["programs_per_decode"] == 4.0
    assert got["decode_ms.attention"] == pytest.approx(4e-6)
    assert got["decode_ms.ffn"] == pytest.approx(8e-6)
    assert got["decode_ms.head"] == pytest.approx(4e-6)
    assert got["decode_scope_coverage"] == pytest.approx(100 * 16 / 25)


def test_no_spans_and_no_map_read_nothing():
    pd = _pd()
    pd.planes[0].lines[0].events = [
        e for e in pd.planes[0].lines[0].events if not e.name.startswith("serve.")]
    got = ss.readings(ss.reduce(pd, None), 100e-9, None)
    assert got == dict.fromkeys(got)      # every reading None


def test_timeline_takes_the_innermost_span():
    tl = ss.Timeline([(0, 100, "serve.step"), (10, 20, "serve.encode"),
                      (12, 14, "serve.encode.wait"), (30, 40, "serve.decode")])
    assert tl.label(5) == ("serve.step", "serve.step")
    assert tl.label(13) == ("serve.encode.wait", "serve.encode")
    assert tl.label(17) == ("serve.encode", "serve.encode")
    assert tl.label(35) == ("serve.decode", "serve.decode")
    assert tl.label(150) == (ss.OUTSIDE, ss.OUTSIDE)


# -- the program on the CPU --------------------------------------------------

def test_scope_map_of_a_tiny_cell_holds_the_model_scopes(tiny_cell):
    import run

    weights = tiny_cell.adapter().make_weights(tiny_cell.conf, 5)
    engine = run.build_engine(tiny_cell, weights)
    scopes = ss.step_programs(
        engine, {"prefill": [(8, 1)], "decode_rows": [1, 2]}, 32)
    assert set(scopes) == {"jit_serve_prefill", "jit_serve_decode"}
    for module, m in scopes.items():
        found = set(m.values())
        assert {"embed", "attention", "ffn.up", "ffn.down", "head"} <= found, (
            module, sorted(found))


def test_scope_of_takes_the_innermost_named_scope():
    assert ss.scope_of("jit(f)/while/body/ffn/ffn.up/jit(_bsr_call)/dot") == "ffn.up"
    assert ss.scope_of("jit(f)/while/body/attention/dot_general") == "attention"
    assert ss.scope_of("jit(f)/while") == ss.UNSCOPED
    assert ss.merge_maps([("m", {"%a": "head"}), ("m", {"%a": "ffn"}),
                          ("m", {"%b": "embed"})]) == {
        "m": {"%a": ss.AMBIGUOUS, "%b": "embed"}}


HLO = """HloModule jit_serve_decode, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(serve_decode)/while/body/ffn/ffn.up/mul"}
}

%body.2 (arg: (s32[], f32[4], bf16[2,4])) -> (s32[], f32[4], bf16[2,4]) {
  %arg = (s32[], f32[4], bf16[2,4]) parameter(0)
  %get-tuple-element.1 = bf16[2,4]{1,0} get-tuple-element(%arg), index=2
  %dynamic-slice.3 = bf16[1,4]{1,0} dynamic-slice(%get-tuple-element.1, %c), metadata={op_name="jit(serve_decode)/while/body/dynamic_slice"}
  %_bsr_call.4 = f32[4]{0} custom-call(%dynamic-slice.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(serve_decode)/while/body/ffn/ffn.up/pallas_call"}
  %fusion.5 = f32[4]{0} fusion(%_bsr_call.4), kind=kLoop, calls=%fused_computation.1
  %dot.6 = f32[4]{0} dot(%fusion.5, %fusion.5), metadata={op_name="jit(serve_decode)/while/body/attention/dot_general"}
  %dynamic-update-slice.7 = bf16[2,4]{1,0} dynamic-update-slice(%get-tuple-element.1, %dot.6, %c)
  ROOT %tuple.8 = (s32[], f32[4], bf16[2,4]) tuple(%c, %dot.6, %dynamic-update-slice.7)
}

ENTRY %main.9 (p__layers____attn____wo__.1: f32[2,4,4]) -> f32[4] {
  %p__layers____attn____wo__.1 = f32[2,4,4]{2,1,0} parameter(0), metadata={op_name="p['layers']['attn']['wo']"}
  %convert.10 = bf16[2,4,4]{2,1,0} convert(%p__layers____attn____wo__.1)
  %tuple.11 = (s32[], bf16[2,4,4]) tuple(%c, %convert.10)
  %while.12 = (s32[], bf16[2,4,4]) while(%tuple.11), condition=%cond, body=%body.2
  %get-tuple-element.13 = bf16[2,4,4]{2,1,0} get-tuple-element(%while.12), index=1
  ROOT %copy.14 = bf16[2,4,4]{2,1,0} copy(%get-tuple-element.13)
}
"""


def test_scope_map_gives_compiler_made_operations_a_scope():
    module, m = ss.scope_map(HLO)
    assert module == "jit_serve_decode"
    got = {k.split(" ", 1)[0]: v for k, v in m.items()}
    assert got["%_bsr_call.4"] == "ffn.up"          # its own op_name
    assert got["%fusion.5"] == "ffn.up"             # its fused instructions
    assert got["%dynamic-slice.3"] == "ffn.up"      # the scoped op using it
    assert got["%dynamic-update-slice.7"] == "attention"  # the op it reads
    assert got["%convert.10"] == "attention"        # the parameter it reads
    assert got["%copy.14"] == ss.UNSCOPED           # only through the loop
    assert "%mul.1" not in got                      # fused: never an op
    assert ss.head("%fusion.5 = f32[4]{0} fusion(f32[4]{0} %_bsr_call.4), "
                   "kind=kLoop") == "%fusion.5 = f32[4]{0} fusion"


def test_a_trace_without_serve_spans_reads_nothing():
    """The stored trace of a program older than its spans (the parent of
    the change that added them) gives no span readings, and no error."""
    pd = trace_reduce.load(BENCH / "traces" / "qwen3_14b.prefill_heavy.xplane.pb.gz")
    red = ss.reduce(pd, None)
    assert red["spans"] == 0 and red["calls"]["decode"] > 0
    got = ss.readings(red, 5.0, None)
    assert all(v is None for v in got.values())


# -- stored traces with their scope maps --------------------------------------

def _replay(path: Path):
    cell = path.name[: -len(".serve_spans.json")]
    doc = json.loads(path.read_text())
    record = json.loads(path.with_name(f"{cell}.record.json").read_text())
    pd = trace_reduce.load(path.with_name(f"{cell}.xplane.pb.gz"))
    lo, hi = record["trace_span"]
    red = ss.reduce(pd, doc["scopes"])
    return pd, doc, record, red, ss.readings(red, hi - lo, doc["scopes"])


def test_a_trace_with_its_scope_map_is_stored():
    assert STORED, "no trace with a scope map under bench/traces"


@pytest.mark.parametrize("path", STORED, ids=[p.name for p in STORED])
def test_stored_trace_replays_its_readings(path):
    _, doc, _, _, got = _replay(path)
    assert set(got) == set(doc["readings"])
    for k, v in got.items():
        assert v is not None, k
        assert v == pytest.approx(doc["readings"][k], rel=1e-9), k


@pytest.mark.parametrize("path", STORED, ids=[p.name for p in STORED])
def test_idle_shares_fit_inside_device_idle(path):
    _, _, record, _, got = _replay(path)
    idle = record["metrics"]["device_idle_share"]["value"]
    parts = [got[f"idle_share.{k}"] for k in ss.IDLE]
    assert all(p >= 0 for p in parts)
    assert sum(parts) <= idle


@pytest.mark.parametrize("path", STORED, ids=[p.name for p in STORED])
def test_scopes_cover_the_decode_program(path):
    _, _, _, red, got = _replay(path)
    assert got["decode_scope_coverage"] >= 90.0
    assert red["self_s"].get(("decode", ss.AMBIGUOUS), 0.0) == 0.0


@pytest.mark.parametrize("path", STORED, ids=[p.name for p in STORED])
def test_programs_per_decode_counted_by_hand(path):
    pd, _, _, red, got = _replay(path)
    spans, launches = ss.host_events(pd)
    decode = [(s, e) for s, e, n in spans if n in ss.DECODE_STAGES]
    waits = [(s, e, n) for s, e, n in spans if n.endswith(ss.WAIT_SUFFIX)]
    n = 0
    for t in launches:
        if any(s <= t <= e for s, e in decode):
            n += 1
        elif any(s <= t <= e for s, e, w in waits
                 if w[: -len(ss.WAIT_SUFFIX)] in ss.DECODE_STAGES):
            n += 1
    assert n > 0
    assert got["programs_per_decode"] == n / red["calls"]["decode"]
