#!/usr/bin/env python3
"""Read a profiler trace by the serving path's own names.

The program marks each engine stage as a host span ``serve.<stage>``
(`repro.serve.executor`: admit, prefill, merge, decode, sample_sync,
encode, retire, ...; the host's waits for device values as the children
``serve.sample_sync.wait`` and ``serve.encode.wait``), and tags the
operations of its step programs with named scopes in their HLO
``op_name`` (``embed``, ``attention``, ``ffn`` with ``ffn.encode``,
``ffn.up``, ``ffn.lif``, ``ffn.down``, ``head``, ``kv_gather``,
``kv_scatter``).  Three reductions, on the trace's clock:

* device idle: each gap between busy intervals goes to the innermost
  ``serve.*`` stage span covering its midpoint (a ``.wait`` span counts
  with the stage around it);
* host program launches (``PJRT_LoadedExecutable_Execute linkage`` on the
  engine's thread): each goes to the innermost stage span at its start;
* device self time (the innermost operation at each instant) of the step
  programs, by named scope.  The trace names an operation only by its HLO
  instruction; the scope comes from the ``op_name`` of the same
  instruction in the program's optimized HLO, or for the instructions the
  compiler makes without one, from the instructions it feeds or reads
  (`scope_map`).  The map is read once per warmed program before the
  traced window and kept beside a stored trace, so that the reduction
  replays off the chip.

`readings` turns them into the seven per-layer numbers of PERF.md §3.  A
trace without ``serve.*`` spans (a program older than its spans) or
without a scope map gives None for what it cannot read; nothing raises.

    python3 bench/serve_spans.py --workload <cell> --seed <n> --seconds <s> --out DIR

runs one traced window of a cell through `run.py` (``--trace 1
--keep-trace DIR``), reads the optimized HLO of every warmed step program
before the window, and writes ``DIR/<cell>.xplane.pb.gz``,
``<cell>.record.json`` and ``<cell>.serve_spans.json`` (the scope map of
the operations in the trace, the readings, and the traced run's
end-to-end numbers): the stored form under ``bench/traces/``.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

SPAN_PREFIX = "serve."
WAIT_SUFFIX = ".wait"
LAUNCH_EVENTS = ("PJRT_LoadedExecutable_Execute linkage",)
SCOPES = ("embed", "attention", "ffn", "ffn.encode", "ffn.up", "ffn.lif",
          "ffn.down", "head", "kv_gather", "kv_scatter")
OUTSIDE = "outside serve spans"
UNSCOPED = "unscoped"
AMBIGUOUS = "?"       # an operation whose scope differs between programs
# idle and launch categories: the stage spans each one collects
IDLE = {"dispatch": ("serve.decode", "serve.prefill"),
        "sample": ("serve.sample_sync",),
        "encode": ("serve.encode",)}
DECODE_STAGES = ("serve.decode", "serve.sample_sync", "serve.encode")
DECODE_SCOPES = {"attention": ("attention",),
                 "ffn": tuple(s for s in SCOPES if s.split(".")[0] == "ffn"),
                 "head": ("head",)}

# "%name = shape opcode(": the head an instruction has both in HLO text and
# in the trace's name of its operation (which prints operand shapes, where
# the text prints operand names alone)
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s.*\{\s*$")
_CALLS = re.compile(r"\bcalls=(%[^\s,]+)")
_REF = re.compile(r"%[\w.\-]+")
# data flow is not followed through these: a loop's or tuple's other
# elements belong to other layers of the model
_BLOCK = ("tuple", "get-tuple-element", "while", "conditional", "call",
          "parameter")
# a step program's parameters are named by their pytree path
# ("p__layers____attn____wq__.1"): which scope reads each
PARAM_SCOPES = (("lm_head", "head"), ("final_norm", "head"),
                ("attn", "attention"), ("ln1", "attention"),
                ("cache", "attention"), ("mlp", "ffn"), ("ln2", "ffn"),
                ("embed", "embed"))


# ---------------------------------------------------------------------------
# scope map: optimized HLO text -> operation -> scope
# ---------------------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The innermost named scope in an ``op_name`` path, or UNSCOPED."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def head(text: str) -> str | None:
    """``"%name = shape opcode"`` of an HLO instruction line or of the
    trace's name for its operation."""
    m = _HEAD.match(text)
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else None


def _operands(rest: str) -> list[str]:
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            end = i
            break
    return _REF.findall(rest[:end])


def scope_map(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction head: scope}) of one program's optimized
    HLO text (`Compiled.as_text()`).

    An instruction's scope is the innermost named scope of its
    ``op_name``.  The compiler makes instructions that carry no scope: a
    fusion takes the scope most common among its fused instructions; any
    other (a weight cast hoisted out of the layer loop, the loop's slice of
    a stacked weight, a copy) takes the scope of the nearest scoped
    instruction that uses it, else of the nearest one it reads, else of the
    parameter it reads (by pytree path), never through a tuple, loop or
    call boundary."""
    module, comp = None, None
    ins = {}             # name -> [head, opcode, scope, operands, comp]
    comps = defaultdict(list)
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HEAD.match(line)
        if not m or comp is None:
            continue
        name, opcode = m.group(1), m.group(3)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else UNSCOPED
        calls = _CALLS.search(line)
        ins[name] = [head(line), opcode, scope,
                     _operands(line[m.end():]), comp,
                     calls.group(1) if calls else None]
        comps[comp].append(name)
    # fusions: the fused instructions' scope
    for v in ins.values():
        if v[1] == "fusion" and v[2] == UNSCOPED and v[5] in comps:
            found = [ins[n][2] for n in comps[v[5]] if ins[n][2] != UNSCOPED]
            if found:
                v[2] = max(set(found), key=found.count)
    own = {n: v[2] for n, v in ins.items()}
    users = defaultdict(list)
    for n, v in ins.items():
        for o in v[3]:
            if o in ins:
                users[o].append(n)

    def nearest(start, step):
        seen, level = {start}, [start]
        while level:
            nxt = []
            for n in level:
                for m in step(n):
                    if m in seen or m not in ins:
                        continue
                    seen.add(m)
                    if own[m] != UNSCOPED:
                        nxt.append(m)
                    elif ins[m][1] not in _BLOCK:
                        nxt.append(m)
            hits = [own[m] for m in nxt if own[m] != UNSCOPED]
            if hits:
                return max(set(hits), key=hits.count)
            level = [m for m in nxt if own[m] == UNSCOPED]
        return None

    def by_param(n):
        for o in _closure(n, lambda x: ins[x][3], ins):
            if ins[o][1] == "parameter":
                for key, sc in PARAM_SCOPES:
                    if key in o:
                        return sc
        return None

    fused = {v[5] for v in ins.values() if v[1] == "fusion"}
    out = {}
    for n, v in ins.items():
        if v[0] is None or v[4] in fused:
            continue
        sc = own[n]
        if sc == UNSCOPED and v[1] not in _BLOCK:
            sc = (nearest(n, lambda x: users[x])
                  or nearest(n, lambda x: ins[x][3]) or by_param(n)
                  or UNSCOPED)
        out[v[0]] = sc
    return module or "", out


def _closure(n, step, ins):
    """Instructions reachable from ``n`` by ``step``, not through _BLOCK
    opcodes (a parameter is reached, not passed)."""
    seen, todo = set(), [n]
    while todo:
        x = todo.pop()
        for m in step(x):
            if m in ins and m not in seen:
                seen.add(m)
                if ins[m][1] not in _BLOCK:
                    todo.append(m)
    return seen


def merge_maps(maps) -> dict:
    """{module: {instruction head: scope}} over several programs; a head
    whose scope differs between programs of one module maps to
    AMBIGUOUS."""
    out: dict = defaultdict(dict)
    for module, m in maps:
        mine = out[module]
        for k, v in m.items():
            if mine.setdefault(k, v) != v:
                mine[k] = AMBIGUOUS
    return dict(out)


def step_programs(engine, warmed: dict, max_len: int) -> dict:
    """Merged scope maps of the engine's prefill and decode programs at
    every warmed shape (``run.warm_up``'s record), compiled as the engine
    compiles them."""
    import jax
    import jax.numpy as jnp

    model = engine.model
    pre = jax.jit(model.prefill, donate_argnums=(2,))
    dec = jax.jit(model.decode, donate_argnums=(2,))

    def cache(rows):
        return jax.eval_shape(lambda: model.init_cache(rows, max_len))

    def scopes(fn, *args):
        return scope_map(engine._engine_scope(
            lambda: fn.lower(*args).compile().as_text())())

    maps = []
    for P, rows in warmed["prefill"]:
        tok = {"tokens": jax.ShapeDtypeStruct((rows, P), jnp.int32)}
        maps.append(scopes(pre, engine.params, tok, cache(rows)))
    for rows in warmed["decode_rows"]:
        tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
        maps.append(scopes(dec, engine.params, tok, cache(rows)))
    return merge_maps(maps)


# ---------------------------------------------------------------------------
# events of a trace
# ---------------------------------------------------------------------------

def host_events(pd):
    """(serve spans [(start, end, name)], launch times [ns]) of the host
    planes."""
    spans, launches = [], []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.end_ns, e.name))
                elif e.name in LAUNCH_EVENTS:
                    launches.append(e.start_ns)
    return sorted(spans, key=lambda s: (s[0], -s[1])), sorted(launches)


def device_events(pd):
    """Per device plane: (module events [(start, end, name)], op events
    [(start, end, name)])."""
    out = []
    for plane in trace_reduce._device_planes(pd):
        mods = trace_reduce._line(plane, trace_reduce.MODULE_LINE)
        ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        out.append((
            sorted((e.start_ns, e.end_ns, e.name)
                   for e in (mods.events if mods is not None else [])),
            sorted(((e.start_ns, e.end_ns, e.name)
                    for e in (ops.events if ops is not None else [])),
                   key=lambda o: (o[0], -o[1])),
        ))
    return out


class Timeline:
    """The innermost ``serve.*`` span at each instant, from properly
    nested spans: ``label(t)`` is (innermost span, innermost stage span —
    a ``.wait`` span's parent), or (OUTSIDE, OUTSIDE)."""

    def __init__(self, spans):
        self.starts, self.segs = [], []
        stack = []

        def emit(a, b):
            if stack and b > a:
                inner = stack[-1][2]
                stage = next((n for _, _, n in reversed(stack)
                              if not n.endswith(WAIT_SUFFIX)), inner)
                self.starts.append(a)
                self.segs.append((a, b, inner, stage))

        t = None
        for s, e, n in spans:
            while stack and stack[-1][1] <= s:
                end = stack[-1][1]
                emit(t, end)
                t = end
                stack.pop()
            if t is not None:
                emit(t, s)
            t = s
            stack.append((s, e, n))
        while stack:
            end = stack[-1][1]
            emit(t, end)
            t = end
            stack.pop()

    def label(self, t) -> tuple[str, str]:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t <= self.segs[i][1]:
            return self.segs[i][2], self.segs[i][3]
        return OUTSIDE, OUTSIDE


def self_times(ops):
    """[(start, self ns, name)] of op events sorted by (start, -end): each
    event's duration less its nested events' (a ``while`` holds its body's
    operations)."""
    out, stack = [], []
    for s, e, n in ops:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([s, e - s, n])
        if stack:
            out[stack[-1][2]][1] -= e - s
        stack.append((s, e, len(out) - 1))
    return [tuple(o) for o in out]


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def reduce(pd, scopes: dict | None) -> dict:
    """Seconds of idle by stage label, launches by stage label, decode
    programs, and device self seconds by (program, scope)."""
    spans, launches = host_events(pd)
    tl = Timeline(spans)
    idle = defaultdict(float)          # (inner, stage) -> s
    launch = defaultdict(int)          # stage -> n
    calls = defaultdict(int)           # program -> n
    busy_prog = defaultdict(float)     # program -> s
    self_s = defaultdict(float)        # (program, scope) -> s
    for t in launches:
        launch[tl.label(t)[1]] += 1
    for mods, ops in device_events(pd):
        for s, e, n in mods:
            prog = trace_reduce._program_of(n)
            if prog is not None:
                calls[prog] += 1
                busy_prog[prog] += (e - s) * 1e-9
        busy = trace_reduce._union([(s, e) for s, e, _ in ops]
                                   or [(s, e) for s, e, _ in mods])
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            idle[tl.label((e0 + s1) / 2)] += (s1 - e0) * 1e-9
        j = 0
        for s, d, n in self_times(ops):
            while j < len(mods) and mods[j][1] < s:
                j += 1
            if not (j < len(mods) and mods[j][0] <= s <= mods[j][1]):
                continue
            prog = trace_reduce._program_of(mods[j][2])
            if prog is None:
                continue
            m = (scopes or {}).get(mods[j][2].split("(", 1)[0], {})
            self_s[(prog, m.get(head(n), UNSCOPED))] += d * 1e-9
    return {"spans": len(spans), "idle_s": dict(idle),
            "launches": dict(launch), "calls": dict(calls),
            "busy_s": dict(busy_prog), "self_s": dict(self_s)}


def readings(red: dict, window_s: float | None, scopes: dict | None) -> dict:
    """The seven numbers (None where the trace or map gives nothing):
    ``idle_share.<dispatch|sample|encode>`` (% of the traced window),
    ``programs_per_decode`` and ``decode_ms.<attention|ffn|head>``, with
    ``decode_scope_coverage`` (% of decode device time they hold)."""
    out = {}
    ok = red["spans"] > 0 and window_s
    for k, stages in IDLE.items():
        s = sum(v for (_, stage), v in red["idle_s"].items() if stage in stages)
        out[f"idle_share.{k}"] = 100.0 * s / window_s if ok else None
    n = red["calls"].get("decode", 0)
    out["programs_per_decode"] = (
        sum(red["launches"].get(s, 0) for s in DECODE_STAGES) / n
        if red["spans"] > 0 and n else None)
    have = bool(scopes) and n > 0
    for k, members in DECODE_SCOPES.items():
        s = sum(v for (p, sc), v in red["self_s"].items()
                if p == "decode" and sc in members)
        out[f"decode_ms.{k}"] = 1e3 * s / n if have else None
    total = red["busy_s"].get("decode", 0.0)
    out["decode_scope_coverage"] = (
        100.0 * sum(out[f"decode_ms.{k}"] for k in DECODE_SCOPES) * n
        / 1e3 / total if have and total > 0 else None)
    return out


def prune(scopes: dict, pd) -> dict:
    """The scope map restricted to the operations the trace executed."""
    seen = {head(n) for _, ops in device_events(pd) for _, _, n in ops}
    return {m: {k: v for k, v in sorted(mp.items()) if k in seen}
            for m, mp in scopes.items()}


# ---------------------------------------------------------------------------
# recording: one traced window, with the scope map of its programs
# ---------------------------------------------------------------------------

def record(workload: str, seed: int, seconds: float, out: Path) -> dict:
    import run
    import spec
    import traffic

    out.mkdir(parents=True, exist_ok=True)
    cell = spec.Spec(BENCH.parent).cell(workload)
    got: dict = {}
    warm_up, read_metrics = run.warm_up, run.read_metrics

    def warm_and_map(engine, cell_, seed_):
        warmed = warm_up(engine, cell_, seed_)
        got["scopes"] = step_programs(engine, warmed,
                                      traffic.max_len(cell_.mix))
        return warmed

    def read_both(entries, rec):
        if rec.trace is not None:
            got["rec"] = rec
            got["end_to_end"] = read_metrics(cell.end_to_end, rec)
        return read_metrics(entries, rec)

    run.warm_up, run.read_metrics = warm_and_map, read_both
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "1",
                       "--keep-trace", str(out)])
    finally:
        run.warm_up, run.read_metrics = warm_up, read_metrics
    if rc != 0 or "rec" not in got:
        raise SystemExit(f"serve_spans: the traced run gave no record (rc {rc})")
    raw = out / f"{workload}.xplane.pb"
    pd = trace_reduce.load(raw)
    lo, hi = got["rec"].window.trace_span
    red = reduce(pd, got["scopes"])
    doc = {"readings": readings(red, hi - lo, got["scopes"]),
           "end_to_end": got["end_to_end"], "reduced": _jsonable(red),
           "scopes": prune(got["scopes"], pd)}
    (out / f"{workload}.serve_spans.json").write_text(json.dumps(doc))
    (out / f"{workload}.xplane.pb.gz").write_bytes(
        gzip.compress(raw.read_bytes()))
    raw.unlink()
    return doc


def _jsonable(red: dict) -> dict:
    return {k: ({" | ".join(kk) if isinstance(kk, tuple) else kk: vv
                 for kk, vv in v.items()} if isinstance(v, dict) else v)
            for k, v in red.items()}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    doc = record(a.workload, a.seed, a.seconds, Path(a.out))
    print(json.dumps({"readings": doc["readings"],
                      "end_to_end": doc["end_to_end"],
                      "reduced": doc["reduced"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
