"""The harness's own arithmetic, on the CPU and without the program:
metric readers, open-loop due-time accounting, analytic counts, traffic
generation and discovery of a cell by name."""
import json
import shutil
import types
from pathlib import Path

import numpy as np
import pytest

import counts
import openloop
import spec
import traffic
from traffic import Request

BENCH = Path(__file__).resolve().parents[1]


def _rec(requests, t0=100.0, seconds=10.0, **kw):
    w = openloop.Window(t0=t0, end=t0 + seconds, requests=requests)
    for k, v in kw.items():
        setattr(w, k, v)
    return types.SimpleNamespace(window=w, requests=requests, setup_s=42.0,
                                 trace=None, dispatches=[])


def _req(i, due, token_t, done=True, refused=False):
    r = Request(i, due, np.zeros(4, np.int32), len(token_t))
    r.token_t = list(token_t)
    r.done_t = token_t[-1] if (done and token_t) else None
    r.refused = refused
    return r


def test_ttft_tail_counts_every_request_from_its_due_time():
    # 20 requests due 0.1 s apart; request i's first token 0.05 * (i + 1)
    # after its due time: p90 over all 20, not over the first 10
    reqs = [_req(i, 0.1 * i, [100.0 + 0.1 * i + 0.05 * (i + 1)])
            for i in range(20)]
    got = spec.metric_module("ttft_p90_ms.queue").compute(_rec(reqs))
    want = np.percentile([0.05 * (i + 1) for i in range(20)], 90) * 1e3
    assert got == pytest.approx(want)


def test_itl_pools_gaps_over_requests():
    reqs = [_req(0, 0.0, [100.0, 100.01, 100.02]),
            _req(1, 0.0, [100.5, 101.5])]
    got = spec.metric_module("itl_p99_ms").compute(_rec(reqs))
    want = np.percentile([0.01, 0.01, 1.0], 99) * 1e3
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,q", [("itl_p99_ms", 99), ("itl_p90_ms", 90)])
def test_itl_percentiles_pool_gaps_over_requests(name, q):
    reqs = [_req(0, 0.0, list(100.0 + 0.01 * np.arange(30))),
            _req(1, 0.0, [100.5, 101.5, 101.6])]
    got = spec.metric_module(name).compute(_rec(reqs))
    want = np.percentile([0.01] * 29 + [1.0, 0.1], q) * 1e3
    assert got == pytest.approx(want)


def test_one_wrong_token_fails_the_worst_request_not_the_mean():
    """Six requests, ~1700 served tokens, one token 4 logits off: the mean
    over the sample stays under a 0.003 limit, the worst request's own
    mean does not."""
    import run

    gaps = [np.zeros(n) for n in (512, 400, 300, 250, 150, 90)]
    gaps[4][7] = 4.0
    st = run.gap_stats(gaps)
    assert st["tokens"] == 1702 and st["flips"] == 1
    assert st["mean_gap"] == pytest.approx(4.0 / 1702)
    assert st["worst_request_gap"] == pytest.approx(4.0 / 150)
    limits = {"mean_logit_gap": 0.003, "worst_request_gap": 0.01}
    ok, checks = run.decide(st, limits, 0)
    assert not ok
    assert checks["mean_logit_gap"]["value"] <= 0.003
    assert checks["worst_request_gap"]["value"] > 0.01
    assert run.decide(run.gap_stats([np.zeros(9)]), limits, 0)[0]
    assert not run.decide(run.gap_stats([np.zeros(9)]), limits, 1)[0]


def test_out_tok_s_counts_only_tokens_inside_the_window():
    # window [100, 110): 3 tokens inside, one before, two after (drain)
    reqs = [_req(0, 0.0, [100.0, 105.0, 109.99, 110.0]),
            _req(1, 0.0, [99.0, 111.0])]
    got = spec.metric_module("out_tok_s").compute(_rec(reqs))
    assert got == pytest.approx(3 / 10.0)


def test_failed_counts_refused_and_unfinished():
    import run

    reqs = [_req(0, 0.0, [100.1, 100.2]),
            _req(1, 0.1, [100.3], done=False),   # never finished
            _req(2, 0.2, [], done=False, refused=True),
            _req(3, 0.3, [], done=False)]        # never started
    assert run.count_failed(reqs) == (1, 2)


def test_decode_rows_per_dispatch_reads_window_deltas():
    rec = _rec([], counters0={"n_decode_rows": 10, "n_decode_batches": 5},
               counters1={"n_decode_rows": 40, "n_decode_batches": 20})
    assert spec.metric_module("decode_rows_per_dispatch").compute(rec) == 2.0
    rec = _rec([], counters0={"n_decode_rows": 0, "n_decode_batches": 0},
               counters1={"n_decode_rows": 0, "n_decode_batches": 0})
    assert spec.metric_module("decode_rows_per_dispatch").compute(rec) is None


def test_trace_metrics_read_nothing_without_a_trace():
    rec = _rec([])
    for name in ("mfu.prefill", "mfu.decode", "bsr_roofline.prefill",
                 "bsr_roofline.decode", "device_idle_share"):
        assert spec.metric_module(name).compute(rec) is None


# -- open loop --------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class _State:
    def __init__(self, rid, n):
        self.rid, self.n, self.generated = rid, n, []

    @property
    def done(self):
        return len(self.generated) >= self.n


class _FakeEngine:
    """Serves one request at a time; each step emits one token and takes
    ``step_s`` of the fake clock."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.queue, self.results = [], {}
        self.cohorts = []
        self.metrics = types.SimpleNamespace(
            n_decode_rows=0, n_decode_batches=0, n_prefill_batches=0,
            n_padded_rows=0)
        self._rid = 0

    def submit(self, prompt, n):
        self._rid += 1
        self.queue.append(_State(self._rid, n))
        return types.SimpleNamespace(rid=self._rid)

    @property
    def idle(self):
        return not self.queue and not self.cohorts

    def step(self):
        if not self.cohorts:
            self.cohorts = [types.SimpleNamespace(slots=[self.queue.pop(0)])]
        st = self.cohorts[0].slots[0]
        self.clock.t += self.step_s
        st.generated.append(7)
        if st.done:
            self.results[st.rid] = st
            self.cohorts = []


def test_open_loop_times_from_due_and_reports_lateness():
    clock = _Clock()
    eng = _FakeEngine(clock, step_s=0.25)
    reqs = [Request(i, 0.1 * i, np.zeros(3, np.int32), 2) for i in range(3)]
    w = openloop.drive(eng, reqs, 1.0, drain_s=5.0, clock=clock,
                       sleep=clock.sleep)
    # request 0 due at 0 runs 2 steps; 1 and 2 are due at 0.1 and 0.2 but
    # the engine is busy: submitted late, served after, TTFT from due
    assert [r.tokens for r in reqs] == [[7, 7]] * 3
    assert reqs[1].submit_t - (w.t0 + reqs[1].due) == pytest.approx(0.15)
    assert reqs[0].token_t[0] - w.t0 == pytest.approx(0.25)
    ttft1 = reqs[1].token_t[0] - (w.t0 + reqs[1].due)
    assert ttft1 == pytest.approx(0.75 - 0.1)
    assert reqs[2].token_t[0] - (w.t0 + reqs[2].due) == pytest.approx(1.25 - 0.2)


def test_open_loop_drains_then_stops():
    clock = _Clock()
    eng = _FakeEngine(clock, step_s=0.25)
    reqs = [Request(0, 0.0, np.zeros(3, np.int32), 20)]
    w = openloop.drive(eng, reqs, 1.0, drain_s=2.0, clock=clock,
                       sleep=clock.sleep)
    # 20 steps of 0.25 s need 5 s; the drain ends at 1 + 2 s
    assert reqs[0].done_t is None
    assert w.stop_t - w.t0 == 3.0
    assert len(reqs[0].token_t) == 12


# -- analytic counts --------------------------------------------------------

TINY = counts.Shapes(D=8, F=16, V=10, L=2, H=2, KV=1, dh=4, T=4, rho=0.5)


def test_counts_by_hand():
    # q 2*8*8 + k,v 4*8*4 + o 2*8*8 = 128 + 128 + 128
    assert counts.proj_flops(TINY) == 384
    # two matrices of 2 * T * rho * D * F = 2 * 4 * 0.5 * 8 * 16 = 512
    assert counts.ffn_flops(TINY) == 1024
    assert counts.head_flops(TINY) == 160
    # prefill of 3 tokens, 2 rows: per row L * (3 * (384 + 1024) + 4*2*4*6)
    # + one head
    assert counts.prefill_flops(TINY, 3, 2) == 2 * (2 * (3 * 1408 + 192) + 160)
    # decode of two rows attending 5 positions each
    assert counts.decode_flops(TINY, [5, 5]) == 2 * (2 * (1408 + 160) + 160)
    f, b = counts.bsr_call(TINY, 3, "up")
    assert f == 2 * 4 * 0.5 * 3 * 8 * 16
    assert b == 0.5 * 8 * 16 * 2 + 3 * 8 * 4 + 3 * 16 * 4 + 3 * 16 * 4
    f, b = counts.bsr_call(TINY, 3, "down")
    assert f == 2 * 4 * 0.5 * 3 * 16 * 8
    assert b == 0.5 * 16 * 8 * 2 + 3 * 16 * 4 + 4 * 3 * 8 * 4 + 3 * 8 * 4
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000.0, 50.0, peak) == (10.0, "compute")
    assert counts.roofline_s(10.0, 50.0, peak) == (5.0, "memory")


# -- traffic ----------------------------------------------------------------

MIX = json.loads((BENCH / "traffic" / "prefill_heavy.json").read_text())


def test_every_seed_gets_the_same_schedule():
    a = traffic.make_requests(MIX, 3.0, 40.0, 32000, 1)
    b = traffic.make_requests(MIX, 3.0, 40.0, 32000, 2 ** 33 + 7)
    assert len(a) == len(b) == 120
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == [
        (r.due, len(r.prompt), r.max_new) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    gaps = np.diff([r.due for r in a])
    assert a[0].due == 0.0 and a[-1].due < 40.0
    assert gaps.mean() == pytest.approx(1 / 3.0)
    assert sum(len(r.prompt) == 1024 for r in a) == 72    # 0.6 of 120
    assert min(r.max_new for r in a) >= 16 and max(r.max_new for r in a) <= 64
    # the lengths are not sorted or grouped in time
    first = [len(r.prompt) for r in a[:60]]
    assert 0 < first.count(2048) < 60


def test_same_seed_same_requests():
    a = traffic.make_requests(MIX, 3.0, 10.0, 32000, 5)
    b = traffic.make_requests(MIX, 3.0, 10.0, 32000, 5)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               and x.max_new == y.max_new for x, y in zip(a, b))


# -- discovery by name ------------------------------------------------------

def test_a_cell_is_added_by_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "mistral-7b-spk.json").read_text())
    conf.update(name="new-model", num_hidden_layers=2)
    (b / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = dict(MIX, name="new_mix")
    (b / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (b / "cells" / "new-model.new_mix.json").write_text(json.dumps(
        {"rate_rps": 1.0, "max_slots": 2, "batch_align": 1,
         "sample_requests": 2,
         "limits": {"mean_logit_gap": 1.0, "worst_request_gap": 1.0}}))
    (b / "metrics" / "new_metric.py").write_text(
        'NAME, UNIT, BETTER, SOURCE = "new_metric", "ms", "lower", "host_clock"\n'
        'LAYER, MOVES = "engine and scheduler", "out_tok_s"\n'
        "def compute(rec):\n    return 3.0\n")
    doc["configs"].append({"name": "new-model", "source": "x",
                           "file": "bench/configs/new-model.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "new-model.new_mix",
                             "config": "new-model", "traffic": "new_mix",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "new_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "engine and scheduler",
                             "moves": "out_tok_s",
                             "workloads": ["new-model.new_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = spec.Spec(tmp_path).cell("new-model.new_mix")
    assert cell.conf["num_hidden_layers"] == 2
    assert cell.mix["name"] == "new_mix"
    assert cell.geometry["max_slots"] == 2
    names = [m["name"] for m in cell.per_layer]
    assert "new_metric" in names and "mfu.prefill" not in names
    mod = spec.load_module(b / "metrics" / "new_metric.py", "metric_new_metric")
    assert mod.compute(None) == 3.0
    old = spec.Spec(BENCH.parent).cell("mistral7b.prefill_heavy")
    assert "new_metric" not in [m["name"] for m in old.per_layer]


def test_metric_files_agree_with_benchmark_json():
    doc = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in doc["end_to_end"] + doc["per_layer"]:
        mod = spec.metric_module(m["name"])
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE) == (
            m["name"], m["unit"], m["better"], m["source"])
        if "layer" in m:
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
    for w in doc["workloads"]:
        cell = spec.Spec(BENCH.parent).cell(w["name"])
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer), w["name"]


def test_benchmark_json_keeps_its_form():
    import re

    doc = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert 1 <= doc["run_seconds"] <= 51
    names = set()
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = json.loads((BENCH.parent / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert name.match(k) and conf["published"][k] != conf[k]
        names.add(c["name"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and name.match(w["name"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])
