"""Reduce a profiler trace (``.xplane.pb``) to device intervals.

Device time is read from the TPU planes: the ``XLA Modules`` line holds
one event per program execution, the ``XLA Ops`` line one per operation
(a Pallas kernel is one operation, named after its kernel).  Programs and
kernels are found by the names in the tables below, as a look at a trace
of the served path on a TPU v5e showed them.  Host spans (the harness's
``bench.*`` annotations) name what the host was doing in each idle gap.
"""
from __future__ import annotations

import gzip
from collections import defaultdict
from dataclasses import dataclass, field

# program -> the module name jit gives it (the harness wraps the model's
# step functions in functions of these names, see run.py)
PROGRAMS = {
    "prefill": "jit_serve_prefill",
    "decode": "jit_serve_decode",
}
# kernel -> (prefix of its operation's name on the XLA Ops line, a
# substring the operation must also hold): the Pallas call is an HLO
# custom call named after the jitted function around it, e.g.
# "%_bsr_call.17 = (f32[4,2048,4096]...) custom-call(...),
# custom_call_target="tpu_custom_call"
KERNELS = {
    "bsr": ("%_bsr_call", "tpu_custom_call"),
}
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
NAME_CHARS = 200      # an operation's name is its HLO text: keep the head


def load(path):
    """ProfileData from a ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Reduced:
    """Device intervals of one trace, in seconds on the trace's clock."""

    n_devices: int = 0
    busy_s: float = 0.0                                   # mean over devices
    program_s: dict = field(default_factory=dict)         # program -> seconds
    program_calls: dict = field(default_factory=dict)     # program -> count
    kernel_s: dict = field(default_factory=dict)          # (program, kernel) -> s
    kernel_calls: dict = field(default_factory=dict)      # (program, kernel) -> n
    top_ops: list = field(default_factory=list)           # [(name, seconds)]
    idle_gaps: list = field(default_factory=list)         # [(host span, s)]
    span_s: float = 0.0                                   # first to last event


def _program_of(name: str):
    for prog, mod in PROGRAMS.items():
        if name == mod or name.startswith(mod + "(") or name.startswith(mod + "."):
            return prog
    return None


def _kernel_of(name: str):
    for k, (prefix, sub) in KERNELS.items():
        if name.startswith(prefix) and sub in name:
            return k
    return None


def _host_spans(pd):
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(HOST_SPAN_PREFIX):
                    spans.append((e.start_ns, e.end_ns, e.name))
    return spans


def _label(spans, t):
    """The innermost host span covering time ``t`` (ns)."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "host: outside the harness's spans"


def reduce(pd, top: int = 10) -> Reduced:
    r = Reduced()
    planes = _device_planes(pd)
    r.n_devices = len(planes)
    op_time = defaultdict(float)
    gaps = defaultdict(float)
    spans = _host_spans(pd)
    busy_total = 0.0
    lo, hi = None, None
    for plane in planes:
        mods = _line(plane, MODULE_LINE)
        ops = _line(plane, OPS_LINE)
        mod_iv = []
        for e in (mods.events if mods is not None else []):
            prog = _program_of(e.name)
            mod_iv.append((e.start_ns, e.end_ns, prog))
            if prog is not None:
                r.program_s[prog] = r.program_s.get(prog, 0.0) + e.duration_ns * 1e-9
                r.program_calls[prog] = r.program_calls.get(prog, 0) + 1
        mod_iv.sort()
        op_iv = []
        j = 0
        for e in sorted(ops.events if ops is not None else [],
                        key=lambda e: e.start_ns):
            op_iv.append((e.start_ns, e.end_ns))
            op_time[e.name] += e.duration_ns * 1e-9
            k = _kernel_of(e.name)
            if k is None:
                continue
            while j < len(mod_iv) and mod_iv[j][1] < e.start_ns:
                j += 1
            prog = None
            if j < len(mod_iv) and mod_iv[j][0] <= e.start_ns <= mod_iv[j][1]:
                prog = mod_iv[j][2]
            key = (prog, k)
            r.kernel_s[key] = r.kernel_s.get(key, 0.0) + e.duration_ns * 1e-9
            r.kernel_calls[key] = r.kernel_calls.get(key, 0) + 1
        busy = _union(op_iv or [(s, e) for s, e, _ in mod_iv])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        if busy:
            lo = busy[0][0] if lo is None else min(lo, busy[0][0])
            hi = busy[-1][1] if hi is None else max(hi, busy[-1][1])
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            gaps[_label(spans, (e0 + s1) / 2)] += (s1 - e0) * 1e-9
    r.busy_s = busy_total / max(1, len(planes))
    r.span_s = ((hi - lo) * 1e-9) if lo is not None else 0.0
    r.top_ops = [(n[:NAME_CHARS], t) for n, t in
                 sorted(op_time.items(), key=lambda kv: -kv[1])[:top]]
    r.idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return r


def describe(pd, n: int = 12) -> str:
    """Planes, lines and their most frequent event names: the one look at
    a trace from which the tables above are written."""
    out = []
    for p in pd.planes:
        out.append(f"plane {p.name!r}")
        for ln in p.lines:
            names = defaultdict(int)
            k = 0
            for e in ln.events:
                names[e.name] += 1
                k += 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:n]
            out.append(f"  line {ln.name!r}: {k} events; {common}")
    return "\n".join(out)
