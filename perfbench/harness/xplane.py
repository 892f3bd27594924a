"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
idle share, time per kernel, the top device ops and what the host was
doing in the longest idle gaps. Read with ``jax.profiler.ProfileData`` and
nothing else. The reduction is the benchmark's: no PR that claims a gain
can change how a trace is read.

Clock: every event's ``start_ns`` is on the profile's one clock, host
threads and device alike, so host spans and device gaps can be laid over
each other.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:CPU$")


@dataclass
class Ev:
    name: str
    start: float        # seconds
    end: float


@dataclass
class Trace:
    device_ops: dict = field(default_factory=dict)   # chip id -> [Ev]
    host: list = field(default_factory=list)         # [Ev], all threads
    lines: list = field(default_factory=list)        # (plane, line, n events)


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, ops_line=OPS_LINE):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        is_host = HOST_PLANE.match(plane.name)
        for line in plane.lines:
            evs = None
            if dev and line.name == ops_line:
                evs = tr.device_ops.setdefault(int(dev.group(1)), [])
            elif is_host:
                evs = tr.host
            n = 0
            for e in line.events:
                n += 1
                if evs is not None:
                    s = e.start_ns * 1e-9
                    evs.append(Ev(e.name, s, s + e.duration_ns * 1e-9))
            tr.lines.append((plane.name, line.name, n))
    for evs in tr.device_ops.values():
        evs.sort(key=lambda e: (e.start, -e.end))
    tr.host.sort(key=lambda e: (e.start, -e.end))
    return tr


def clip(evs, t0, t1):
    return [Ev(e.name, max(e.start, t0), min(e.end, t1)) for e in evs
            if e.end > t0 and e.start < t1]


def union(evs):
    """Merged busy intervals [(start, end)] of events sorted by start."""
    out = []
    for e in evs:
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1][1] = e.end
        else:
            out.append([e.start, e.end])
    return out


def busy_seconds(evs):
    return sum(b - a for a, b in union(evs))


def gaps(evs, t0, t1):
    """Idle intervals of [t0, t1] not covered by any event."""
    out, at = [], t0
    for a, b in union(clip(evs, t0, t1)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def self_times(evs):
    """Seconds per op name, each instant given to the innermost event that
    covers it (a ``while`` does not also count its body's ops)."""
    total = {}
    stack = []                      # [ev, covered-by-children seconds]

    def close(upto):
        while stack and stack[-1][0].end <= upto:
            ev, child = stack.pop()
            dur = ev.end - ev.start
            total[ev.name] = total.get(ev.name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][1] += dur

    for e in evs:
        close(e.start)
        stack.append([e, 0.0])
    close(float("inf"))
    return total


def matching_seconds(evs, pattern):
    """Summed device time of the events whose name matches ``pattern``; an
    event nested in another that matched is not counted twice."""
    rx = re.compile(pattern)
    total, n, until = 0.0, 0, -1.0
    for e in evs:
        if rx.search(e.name) and e.start >= until:
            total += e.end - e.start
            n += 1
            until = e.end
    return total, n


def host_activity(host, t):
    """What the host was doing at instant t: the benchmark's own span
    (``bench/...``) and, inside it, the shortest other host event."""
    outer, inner, inner_len = None, None, float("inf")
    for e in host:
        if e.start > t:
            break
        if e.end < t:
            continue
        if e.name.startswith("bench/"):
            if outer is None or (e.end - e.start) < outer[1]:
                outer = (e.name, e.end - e.start)
        elif (e.end - e.start) < inner_len:
            inner, inner_len = e.name, e.end - e.start
    name = outer[0] if outer else "outside bench spans"
    return f"{name}>{inner}" if inner else name


STEP_SPAN = re.compile(r"^bench/.*step$")
_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])?[^ ]* ?.*? ([a-z][\w\-]*)\(")


def short_name(name):
    """'%fusion.126 = bf16[64,128,152064]{...} fusion(...)' ->
    '%fusion fusion bf16[64,128,152064]': a device op's name is its whole HLO
    line; the breakdown keeps the instruction's name without its instance
    number, its opcode and its result's shape, so the copies of one op in
    the unrolled layers add up under one entry."""
    mt = _HLO.match(name)
    if not mt:
        return name[:120]
    shape = (mt.group(2) or "").lstrip("(")
    inst = re.sub(r"\.\d+", "", mt.group(1))
    return f"{inst} {mt.group(3)} {shape}".strip()[:120]


def step_spans(host):
    return [e for e in host if STEP_SPAN.match(e.name)]


def window_of(host):
    """Start of the first step span counted to the end of the last. The
    first step span after the profiler starts is left out where more than
    one follows: engaging the device tracer can stall the device once (2.3 s
    with the host waiting inside one turn, in one traced run of nine, PR 23)
    — the tracing's cost, not the program's idle time."""
    evs = sorted(step_spans(host), key=lambda e: e.start)
    if not evs:
        return None
    if len(evs) > 2:
        evs = evs[1:]
    return evs[0].start, max(e.end for e in evs)


def reduce(tr, window=None, top=10):
    """busy_s (mean over chips), window_s, top device ops by self time and
    idle gaps by host activity."""
    if not tr.device_ops:
        raise ValueError("the trace has no device plane with an "
                         f"'{OPS_LINE}' line: {tr.lines}")
    if window is None:
        window = window_of(tr.host)
    if window is None:
        starts = [e.start for evs in tr.device_ops.values() for e in evs]
        ends = [e.end for evs in tr.device_ops.values() for e in evs]
        window = (min(starts), max(ends))
    t0, t1 = window
    busy, ops, gap_by = [], {}, {}
    for chip, evs in sorted(tr.device_ops.items()):
        c = clip(evs, t0, t1)
        busy.append(busy_seconds(c))
        for k, v in self_times(c).items():
            k = short_name(k)
            ops[k] = ops.get(k, 0.0) + v / len(tr.device_ops)
    first = tr.device_ops[min(tr.device_ops)]
    host = [e for e in tr.host if e.end > t0 and e.start < t1]
    for a, b in sorted(gaps(first, t0, t1), key=lambda g: g[0] - g[1])[:200]:
        k = host_activity(host, (a + b) / 2)
        gap_by[k] = gap_by.get(k, 0.0) + (b - a)
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    spans = step_spans(tr.host)
    steps = sum(1 for e in spans if e.start >= t0 and e.end <= t1)
    # idle share of every step span the trace holds, the settling ones too:
    # where a stall sits shows here
    step_idle = [round(100.0 * (1.0 - busy_seconds(clip(first, e.start, e.end))
                                / (e.end - e.start)), 1)
                 for e in spans if e.end > e.start]
    return {"busy_s": sum(busy) / len(busy), "window_s": t1 - t0,
            "window": (t0, t1), "steps": steps, "step_idle": step_idle,
            "device_ops": rank(ops), "idle_gaps": rank(gap_by)}


def summary(tr, n=40):
    """For a human: the planes and lines, and the device ops by self time."""
    out = [f"{p} | {l} | {k} events" for p, l, k in tr.lines]
    for chip, evs in sorted(tr.device_ops.items()):
        st = sorted(self_times(evs).items(), key=lambda kv: -kv[1])[:n]
        out.append(f"-- chip {chip}: {len(evs)} op events, busy "
                   f"{busy_seconds(evs):.4f} s")
        out += [f"   {v:10.6f} s  {k}" for k, v in st]
    return "\n".join(out)
