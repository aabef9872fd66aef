#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with weights drawn on the device from the
seed, serves the cell's traffic through `repro.serve.Engine` (the policy
`ExecutionPolicy.for_arch` picks: packed spikes, dual-sparse BSR join
plans, one device, bitwise, sync) as an open-loop client on the wall
clock, then checks what the window served against the plain float32
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of part of the window), ``device``, and last ``checks``: each number
compared, with its limit.  Exits non-zero with no result line when JAX's
first device is not a TPU or there are fewer chips than the cell needs.

Options for building the benchmark (the measured runs use none of them):
``--rates`` measures several rates in one process for the knee sweep (no
check); ``--control 1`` also puts each broken reference (the float8
control, two faults confined to the FFN) in the program's place on the
checked sample and decides ``correct`` for it as for the program;
``--keep-trace DIR`` keeps the profile.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "metrics"))

import counts  # noqa: E402
import openloop  # noqa: E402
import spec as spec_mod  # noqa: E402
import traffic  # noqa: E402

DRAIN_S = 60.0        # a request due in the window may finish this late
TRACE_AT = 0.25       # traced span: from this share of the window ...
TRACE_LEN_S = 5.0     # ... for this long (or to the window's end)
OUT_DIR = ROOT / ".bench_out"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def require_chips(n: int):
    """JAX's devices, after checking that the first is a TPU and that there
    are ``n`` of them.  Exits non-zero otherwise: no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is {devs[0].platform} "
                         f"({devs[0].device_kind}), not a TPU; no result")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees {len(devs)}")
    return devs


def configure_jax(root: Path) -> str:
    """Persistent compile cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), holding every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def import_program(root: Path):
    sys.path.insert(0, str(root / "src"))
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"bench: the program is not here ({e}); no result")


@dataclass
class Record:
    """What the metric readers read."""

    window: object
    requests: list
    dispatches: list
    shapes: counts.Shapes
    peak: dict
    setup_s: float
    trace: object = None


class Tracer:
    """Starts and stops the profiler at step boundaries, with the device
    drained on both sides."""

    def __init__(self, engine, path: Path, start_at: float, stop_at: float):
        self.engine, self.path = engine, path
        self.start_at, self.stop_at = start_at, stop_at

    def _drain(self):
        import jax

        jax.block_until_ready([c.cache for c in self.engine.cohorts])

    @staticmethod
    def _options():
        """Device and host events; no Python function tracer (it records
        every Python call: host overhead, and most of the file)."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        return opts

    def warm(self):
        """The profiler's first start loads its plugin, seconds long: do it
        in set-up, not in the window."""
        import jax

        jax.profiler.start_trace(str(self.path / "warm"),
                                 profiler_options=self._options())
        jax.profiler.stop_trace()
        shutil.rmtree(self.path / "warm", ignore_errors=True)

    def start(self) -> float:
        """Start tracing; returns the host clock at which the span opens."""
        import jax

        self._drain()
        jax.profiler.start_trace(str(self.path),
                                 profiler_options=self._options())
        return time.perf_counter()

    def stop(self) -> float:
        """Stop tracing; returns the host clock at which the span closed
        (before the profiler writes its file)."""
        import jax

        self._drain()
        t = time.perf_counter()
        jax.profiler.stop_trace()
        return t


def named_steps(model):
    """The model with its two step functions under stable names, so that
    their programs are found in a trace (jit names a program after its
    function; the model's are lambdas)."""
    prefill, decode = model.prefill, model.decode

    def serve_prefill(p, batch, cache):
        return prefill(p, batch, cache)

    def serve_decode(p, tokens, cache):
        return decode(p, tokens, cache)

    return replace(model, prefill=serve_prefill, decode=serve_decode)


def build_engine(cell, weights):
    from repro.models.registry import build_model
    from repro.serve import Engine, ExecutionPolicy

    ad = cell.adapter()
    cfg = ad.arch_config(cell.conf)
    policy = ExecutionPolicy.for_arch(cfg)
    if not (policy.spike_format == "packed"
            and policy.weight_sparsity == "dual_sparse"):
        raise SystemExit(f"bench: for_arch picked {policy.describe()}, not "
                         "packed dual-sparse")
    g = cell.geometry
    return Engine(named_steps(build_model(cfg)), weights,
                  max_len=traffic.max_len(cell.mix), max_slots=g["max_slots"],
                  batch_align=g["batch_align"], max_queue=4096, policy=policy)


def merges_possible(mix: dict) -> bool:
    """Cohorts merge only at equal positions: two prompt lengths closer
    than the longest output can meet."""
    p = sorted(set(mix["prompt_len"]["values"]))
    return any(b - a < mix["output_len"]["max"] for a, b in zip(p, p[1:]))


def warm_up(engine, cell, seed: int) -> dict:
    """Compile (or load) every program the cell's traffic can reach.

    For each prompt length and each prefill group size, one group of
    requests with outputs of 2, 3, ... tokens runs to the end: the group's
    prefill, its first decode (alignment rows included), then one retire
    and one decode per step down to a single row, with the sampling, spike
    encode and row gathers of each size.  Then every row gather n -> m and,
    where the mix lets cohorts merge, every two-cohort merge of a + b
    rows."""
    import jax

    rng = np.random.default_rng([seed, 1])
    S = cell.geometry["max_slots"]
    vocab = cell.conf["vocab_size"]
    shapes = {"prefill": set(), "decode_rows": set()}
    align = engine.batch_align
    for P in sorted(set(cell.mix["prompt_len"]["values"])):
        for g in range(1, S + 1):
            t = time.perf_counter()
            for i in range(g):
                engine.submit(rng.integers(0, vocab, size=P, dtype=np.int32),
                              2 + i)
            engine.run()
            log(f"warm-up: prompt {P} x {g}: {time.perf_counter() - t:.2f}s")
            shapes["prefill"].add((P, g + (-g) % align))
            shapes["decode_rows"].update([g + (-g) % align, *range(1, g)])
    ops = engine.cache_ops
    full = engine.model.init_cache(S, engine.max_len)
    by_rows = {n: ops.take(full, list(range(n))) for n in range(1, S + 1)}
    for n in range(2, S + 1):
        for m in range(1, n):
            ops.take(by_rows[n], list(range(m)))
    n_merge = 0
    if merges_possible(cell.mix):
        for a in range(1, S):
            for b in range(1, S - a + 1):
                ops.concat([by_rows[a], by_rows[b]])
                n_merge += 1
    jax.block_until_ready(list(by_rows.values()))
    return {"prefill": sorted(shapes["prefill"]),
            "decode_rows": sorted(shapes["decode_rows"]),
            "row_gathers": S * (S - 1) // 2 + S, "merges": n_merge}


def sample_requests(requests, n: int, seed: int):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in requests if r.done_t is not None and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), len(r.prompt), -r.index))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    k = min(n - 1, len(others))
    pick = rng.choice(len(others), size=k, replace=False) if k else []
    return [longest] + [others[i] for i in sorted(pick)]


def check_served(cell, weights, sample, variants=()) -> dict:
    """How far each served token's logit lies below the reference's best
    at its position, for every token of the sample; with ``variants``,
    the same for the tokens each broken reference puts first.  Returns
    `gap_stats` per source ("program" and each variant) and the FFN spike
    rates the reference read.

    The widest gap, which a served model's check would compare first,
    does not separate the program from the control here: both are the
    margin of the rare near-tie that rounding flips, and on Qwen3-14B the
    program's widest (0.073) reached half the control's smallest (0.151).
    The mean counts how many tokens flip and by how much: the program
    flips 2-7 % of tokens by little, the control 8-30 % by more.  A mean
    over the whole sample dilutes one wrong request, so the worst
    request's own mean is compared beside it (PERF.md gives the
    readings)."""
    ref = cell.reference()
    seq_len = traffic.max_len(cell.mix)
    n_rows = cell.mix["output_len"]["max"]
    gaps = {"program": [], **{v: [] for v in variants}}
    rates = []
    for r in sample:
        toks = np.asarray(r.tokens)
        at = np.arange(toks.shape[0])
        logits, rate = ref.served_logits(cell.conf, weights, r.prompt, toks,
                                          seq_len, n_rows)
        best = logits.max(-1)
        gaps["program"].append(best - logits[at, toks])
        rates.append(rate)
        for v in variants:
            c, _ = ref.served_logits(cell.conf, weights, r.prompt, toks,
                                     seq_len, n_rows, variant=v)
            gaps[v].append(best - logits[at, c.argmax(-1)])
    out = {k: gap_stats(g) for k, g in gaps.items()}
    out["spike_rates"] = {"ffn_input": float(np.mean([r[0] for r in rates])),
                          "ffn_hidden": float(np.mean([r[1] for r in rates]))
                          } if rates else {}
    return out


def gap_stats(gaps) -> dict:
    """The numbers compared: the mean gap over every token, and the worst
    request's mean gap; besides, the widest gap and how many tokens sit
    off the reference's argmax."""
    g = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"mean_gap": float(g.mean()),
            "worst_request_gap": float(max((x.mean() for x in gaps),
                                           default=0.0)),
            "max_gap": float(g.max()), "flips": int((g > 0).sum()),
            "tokens": int(g.shape[0])}


def decide(stats: dict, limits: dict, unfinished: int) -> tuple[bool, dict]:
    """``correct`` and the numbers it was decided on, each with its
    limit."""
    checks = {
        "mean_logit_gap": {"value": stats["mean_gap"],
                           "limit": limits["mean_logit_gap"]},
        "worst_request_gap": {"value": stats["worst_request_gap"],
                              "limit": limits["worst_request_gap"]},
        "unfinished_requests": {"value": unfinished, "limit": 0},
    }
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def device_info(devs, trace_red=None, span=None) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace_red is not None and span is not None:
        info["busy_s"] = trace_red.busy_s
        info["window_s"] = span[1] - span[0]
    return info


def lateness(w) -> dict:
    late = [r.submit_t - (w.t0 + r.due) for r in w.requests
            if r.submit_t is not None]
    if not late:
        return {"p50_ms": None, "max_ms": None}
    return {"p50_ms": float(np.median(late)) * 1e3,
            "max_ms": float(np.max(late)) * 1e3}


class GcPauses:
    """Wall time of the interpreter's garbage collections, by generation,
    between ``start()`` and ``stop()``: a host pause that ITL reads."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))
            self._t = None

    def start(self):
        gc.callbacks.append(self._cb)

    def stop(self) -> dict:
        gc.callbacks.remove(self._cb)
        gen2 = [d for g, d in self.pauses if g == 2]
        return {"collections": len(self.pauses), "gen2": len(gen2),
                "longest_ms": max((d for _, d in self.pauses), default=0.0) * 1e3}


def itl_profile(requests, dispatches) -> dict:
    """Where the gaps between tokens lie: percentiles, and the share of
    gaps in which the engine also ran a prefill (those form the tail)."""
    pre = np.sort([d.t for d in dispatches if d.kind == "prefill"])
    gaps, stalled = [], 0
    for r in requests:
        for a, b in zip(r.token_t, r.token_t[1:]):
            gaps.append(b - a)
            stalled += int(np.searchsorted(pre, b) > np.searchsorted(pre, a))
    if not gaps:
        return {}
    q = np.percentile(gaps, [50, 90, 95, 98, 99, 100]) * 1e3
    return {"gaps": len(gaps), "with_prefill_share": stalled / len(gaps),
            **{f"p{k}_ms": float(v) for k, v in zip((50, 90, 95, 98, 99, 100), q)}}


def keep_record(path: Path, rec, metrics) -> None:
    """What the per-layer readers read besides the trace: with the kept
    profile, enough to compute the same metrics again off the chip."""
    w = rec.window
    doc = {"trace_span": list(w.trace_span), "t0": w.t0, "end": w.end,
           "counters0": w.counters0, "counters1": w.counters1,
           "dispatches": [[d.kind, d.t, d.rows, d.live, d.length]
                          for d in rec.dispatches],
           "peak": rec.peak, "metrics": metrics}
    path.write_text(json.dumps(doc))


def count_failed(requests) -> tuple[int, int]:
    """(refused at submit, not finished when the drain ended)."""
    refused = sum(1 for r in requests if r.refused)
    unfinished = sum(1 for r in requests if not r.refused and r.done_t is None)
    return refused, unfinished


def read_metrics(entries, rec) -> dict:
    out = {}
    for m in entries:
        mod = spec_mod.metric_module(m["name"])
        v = mod.compute(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def sweep(cell, engine, rates, seconds: float, seed: int, compiles) -> None:
    """Knee sweep: one window per rate in this process, backlog printed."""
    for i, rate in enumerate(rates):
        reqs = traffic.make_requests(cell.mix, rate, seconds,
                                     cell.conf["vocab_size"], seed + i)
        w = openloop.drive(engine, reqs, seconds, drain_s=DRAIN_S,
                           compiles=compiles)
        out = [n for t, n in w.outstanding if t < seconds]
        q = len(out) // 4
        first, last = (np.mean(out[q:2 * q]) if q else 0.0,
                       np.mean(out[-q:]) if q else 0.0)
        ttft = [r.token_t[0] - (w.t0 + r.due) for r in reqs if r.token_t]
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "outstanding_q2": float(first), "outstanding_q4": float(last),
            "outstanding_end": out[-1] if out else 0,
            "ttft_p50_ms": float(np.median(ttft)) * 1e3 if ttft else None,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3 if ttft else None,
            "drain_s": w.stop_t - w.end, "compiles": w.compiles_in_window,
            "late": lateness(w)}), flush=True)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             devs, control: bool = False,
             keep_trace: str | None = None, rates=None, peaks=None) -> dict:
    import jax

    compiles = openloop.CompileCounter()
    conf = cell.conf
    shp = counts.Shapes.of(conf)
    kind = devs[0].device_kind
    peaks = peaks if peaks is not None else spec_mod.load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    ad = cell.adapter()
    if devs[0].platform == "tpu":
        ad.check_program_constants(conf)
    t = time.perf_counter()
    weights = ad.make_weights(conf, seed)
    jax.block_until_ready(weights)
    t_w = time.perf_counter() - t
    t = time.perf_counter()
    engine = build_engine(cell, weights)
    jax.block_until_ready(engine.params)
    t_e = time.perf_counter() - t
    t = time.perf_counter()
    warmed = warm_up(engine, cell, seed)
    t_u = time.perf_counter() - t
    log(f"setup: weights {t_w:.2f}s, engine (join plans) {t_e:.2f}s, "
        f"warm-up {t_u:.2f}s; warmed {warmed}; executables built "
        f"{compiles.built}, compiled (cache misses) {compiles.misses}")
    rate = cell.geometry["rate_rps"]
    if rates:
        sweep(cell, engine, rates, seconds, seed, compiles)
        return {}
    reqs = traffic.make_requests(cell.mix, rate, seconds, conf["vocab_size"], seed)
    recorder = openloop.Recorder(engine, annotate=trace)
    tracer = None
    if trace:
        tdir = OUT_DIR / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        start = TRACE_AT * seconds
        tracer = Tracer(engine, tdir, start, min(seconds, start + TRACE_LEN_S))
        tracer.warm()
    setup_s = time.perf_counter() - t_start
    pauses = GcPauses()
    pauses.start()
    w = openloop.drive(engine, reqs, seconds, drain_s=DRAIN_S,
                       compiles=compiles, tracer=tracer, recorder=recorder)
    gc_in_window = pauses.stop()
    dev = device_info(devs)
    late = lateness(w)
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
        f"memory_peak_bytes {dev['memory_peak_bytes']}")
    log(f"window: {len(reqs)} requests due at {rate} req/s over {seconds}s, "
        f"{w.steps} engine steps; executables built in the window "
        f"{w.compiles_in_window} (compiled {w.cache_misses_in_window}); "
        f"drain {w.stop_t - w.end:.2f}s; generator lateness p50 "
        f"{late['p50_ms']} ms, max {late['max_ms']} ms")
    log(f"token gaps: {json.dumps(itl_profile(reqs, recorder.log))}; "
        f"garbage collections {json.dumps(gc_in_window)}")
    red = None
    if trace:
        import trace_reduce

        files = sorted(tdir.rglob("*.xplane.pb"))
        red = trace_reduce.reduce(trace_reduce.load(files[-1]))
        if keep_trace:
            Path(keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], Path(keep_trace) / f"{cell.name}.xplane.pb")
        shutil.rmtree(tdir, ignore_errors=True)
        dev = device_info(devs, red, w.trace_span)
        log(f"trace: {red.program_calls} program calls, program seconds "
            f"{red.program_s}, kernel seconds {red.kernel_s}, kernel calls "
            f"{red.kernel_calls}, busy {red.busy_s!r}s of "
            f"{dev.get('window_s')!r}s")
    rec = Record(w, reqs, recorder.log, shp, peaks[kind], setup_s, red)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, rec)
    if trace and keep_trace:
        keep_record(Path(keep_trace) / f"{cell.name}.record.json", rec, metrics)
    refused, unfinished = count_failed(reqs)
    # free the program's state before the reference runs: the peak above
    # is the program's alone
    del engine, recorder, tracer
    gc.collect()
    t = time.perf_counter()
    sample = sample_requests(reqs, cell.geometry["sample_requests"], seed)
    chk = check_served(cell, weights, sample,
                       variants=cell.reference().VARIANTS if control else ())
    t_ref = time.perf_counter() - t
    limits = cell.geometry["limits"]
    correct, checks = decide(chk["program"], limits, unfinished)
    correct = correct and bool(sample)
    log(f"reference: {len(sample)} requests, {chk['program']['tokens']} "
        f"served tokens compared in {t_ref:.2f}s; FFN spike rates "
        f"{json.dumps(chk['spike_rates'])}")
    log(f"program gaps: {json.dumps(chk['program'])}")
    controls = {}
    for v in chk:
        if v in ("program", "spike_rates"):
            continue
        ok, _ = decide(chk[v], limits, 0)
        controls[v] = {"correct": ok, "gaps": chk[v]}
        log(f"broken reference {v}: correct {ok}; gaps {json.dumps(chk[v])}")
    result = {
        "correct": correct,
        "attempted": len(reqs),
        "failed": refused + unfinished,
        "metrics": metrics,
        "device": dev,
        "window": {"compiles": w.compiles_in_window,
                   "compiled": w.cache_misses_in_window,
                   "steps": w.steps, "lateness": late,
                   "drain_s": w.stop_t - w.end, "warmed": warmed},
    }
    if control:
        result["controls"] = controls
    if trace and red is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps],
        }
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a whole number >= 0")
    cell = spec_mod.Spec(ROOT).cell(args.workload)
    devs = require_chips(cell.chips)
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    log(f"compile cache: {configure_jax(ROOT)}")
    import_program(ROOT)
    rates = [float(x) for x in args.rates.split(",")] if args.rates else None
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, devs=devs,
                      control=bool(args.control),
                      keep_trace=args.keep_trace, rates=rates)
    if not result:
        return 0
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
