"""The per-layer metrics from a short trace recorded on a TPU v5e.

``bench/traces/<cell>.xplane.pb.gz`` is the profile of a traced run's
span, ``<cell>.record.json`` what that run logged besides (dispatches,
trace span, counters) and the metrics it printed.  Reducing the stored
trace again must give the same numbers, so every later change computes
them the same way.
"""
import gzip
import json
from pathlib import Path

import pytest

import counts
import openloop
import run
import spec
import trace_reduce

BENCH = Path(__file__).resolve().parents[1]
RECORDS = sorted((BENCH / "traces").glob("*.record.json"))


def _replay(path: Path):
    doc = json.loads(path.read_text())
    name = path.name[: -len(".record.json")]
    cell = spec.Spec(BENCH.parent).cell(name)
    red = trace_reduce.load(path.with_name(f"{name}.xplane.pb.gz"))
    red = trace_reduce.reduce(red)
    w = openloop.Window(t0=doc["t0"], end=doc["end"], requests=[])
    w.trace_span = tuple(doc["trace_span"])
    w.counters0, w.counters1 = doc["counters0"], doc["counters1"]
    rec = run.Record(w, [], [openloop.Dispatch(*d) for d in doc["dispatches"]],
                     counts.Shapes.of(cell.conf), doc["peak"], 0.0, red)
    return cell, doc, red, run.read_metrics(cell.per_layer, rec)


def test_a_trace_is_stored():
    assert RECORDS, "no recorded trace under bench/traces"


@pytest.mark.parametrize("path", RECORDS, ids=[p.name for p in RECORDS])
def test_stored_trace_gives_the_recorded_metrics(path):
    cell, doc, red, got = _replay(path)
    assert red.n_devices == 1
    assert red.program_calls.get("prefill", 0) + red.program_calls.get("decode", 0) > 0
    # every metric the cell lists now that the trace and counters give, as
    # that run computed it (the run may have printed more: a metric the
    # cell has since dropped); the record keeps no request's clock, so a
    # host-clock metric reads nothing here
    assert set(got) == {m["name"] for m in cell.per_layer
                        if m["source"] != "host_clock"}
    for k, v in got.items():
        assert v["value"] == pytest.approx(doc["metrics"][k]["value"],
                                           rel=1e-9), k
    for k, v in got.items():
        if "roofline" in k or "mfu" in k:
            assert 0 < v["value"] <= 100, k


@pytest.mark.parametrize("path", RECORDS, ids=[p.name for p in RECORDS])
def test_kernel_calls_match_the_dispatches(path):
    cell, doc, red, _ = _replay(path)
    L = cell.conf["num_hidden_layers"]
    lo, hi = doc["trace_span"]
    for prog in ("prefill", "decode"):
        n = sum(1 for d in doc["dispatches"] if d[0] == prog and lo <= d[1] <= hi)
        assert red.program_calls.get(prog, 0) == n
        assert red.kernel_calls.get((prog, "bsr"), 0) == 2 * L * n


def test_gzip_and_plain_traces_load_alike(tmp_path):
    src = RECORDS[0].with_name(RECORDS[0].name[: -len(".record.json")]
                               + ".xplane.pb.gz")
    plain = tmp_path / "t.xplane.pb"
    plain.write_bytes(gzip.decompress(src.read_bytes()))
    a = trace_reduce.reduce(trace_reduce.load(src))
    b = trace_reduce.reduce(trace_reduce.load(plain))
    assert a.busy_s == b.busy_s and a.program_s == b.program_s
