"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to device busy time,
the device operations that took most of it, and the idle gaps by what the
clients had in flight.  Reads the file with JAX alone."""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
CLIENT_MARK = "stmt:"          # the harness's TraceAnnotation around each statement


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def covered(intervals: list) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def gaps(busy: list, start: float, end: float) -> list:
    """The idle [start, end) stretches of a window, given merged busy intervals."""
    out, at = [], start
    for s, e in busy:
        if s > at:
            out.append([at, min(s, end)])
        at = max(at, e)
        if at >= end:
            break
    if at < end:
        out.append([at, end])
    return [g for g in out if g[1] > g[0]]


def op_name(event_name: str) -> str:
    """The TPU's ops line names an event by its whole HLO instruction
    (`%fusion.7 = (...) fusion(...)`): keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def device_events(profile) -> dict:
    """{plane name: [(op name, start_ns, end_ns)]} for every device plane;
    its `XLA Ops` line where it has one, else all its lines."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        chosen = [ln for ln in lines if ln.name == OPS_LINE] or lines
        out[plane.name] = [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ln in chosen for ev in ln.events if ev.duration_ns > 0]
    return out


def _host_events(profile, keep) -> list:
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in ln.events if ev.duration_ns > 0 and keep(ln.name, ev.name)]
    return out


def client_marks(profile) -> list:
    """[(statement name, start_ns, end_ns)] of the harness's annotations."""
    return [(name[len(CLIENT_MARK):], s, e)
            for name, s, e in _host_events(profile, lambda _ln, ev: ev.startswith(CLIENT_MARK))]


def host_calls(profile) -> list:
    """[(name, start_ns, end_ns)] of what the profiler's host tracer saw the
    process's Python threads do: `PjitFunction(program)`, `np.asarray(jax.Array)`."""
    return [(name[:60], s, e) for name, s, e in _host_events(
        profile, lambda ln, ev: ln.startswith("python") and not ev.startswith(CLIENT_MARK))]


def mark_segments(marks: list, lo: float, hi: float) -> list:
    """[lo, hi) cut at every start and end of a mark: sorted
    [(start, end, label)], the label naming the marks that cover the
    segment, "" where none does."""
    edges = sorted({lo, hi} | {t for _, s, e in marks for t in (s, e) if lo < t < hi})
    starts = sorted((s, m) for m, s, _ in marks)
    ends = sorted((e, m) for m, _, e in marks)
    live, i, j, out = collections.Counter(), 0, 0, []
    for a, b in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][0] <= a:
            live[starts[i][1]] += 1
            i += 1
        while j < len(ends) and ends[j][0] <= a:
            live[ends[j][1]] -= 1
            j += 1
        out.append((a, b, "+".join(sorted(m for m, n in live.items() if n > 0))))
    return out


def pieces(intervals: list, segments: list):
    """The sorted `intervals` cut by the sorted `segments`: (start, end, label)."""
    ends = [seg[1] for seg in segments]
    for g0, g1 in intervals:
        k = bisect.bisect_right(ends, g0)
        while k < len(segments) and segments[k][0] < g1:
            yield max(g0, segments[k][0]), min(g1, segments[k][1]), segments[k][2]
            k += 1


def idle_by_host(idle: list, calls: list, statements: list) -> collections.Counter:
    """Idle time by what the host was doing: the traced host call that
    covers it; where none does, the statements the clients had in flight."""
    out = collections.Counter()
    for a, b, call in pieces(idle, calls):
        if call:
            out[call] += b - a
            continue
        for c, d, waiting in pieces([[a, b]], statements):
            out[f"no traced host call, in flight: {waiting}" if waiting else "no statement in flight"] += d - c
    return out


def reduce(profile, window_s: float) -> dict | None:
    """busy_s (mean over device planes), the ten device operations with
    most time, and idle time by what the host was doing (the ten
    largest).  None where no operation ran on a device."""
    planes = {k: v for k, v in device_events(profile).items() if v}
    if not planes:
        return None
    marks, calls = client_marks(profile), host_calls(profile)
    by_op = collections.Counter()
    idle_by = collections.Counter()
    busy_total = 0.0
    for events in planes.values():
        spans = [(s, e) for _, s, e in events]
        busy_total += covered(spans)
        for name, s, e in events:
            by_op[name] += e - s
        lo = min([s for s, _ in spans] + [s for _, s, _ in marks])
        hi = max([e for _, e in spans] + [e for _, _, e in marks])
        idle_by += idle_by_host(gaps(union(spans), lo, hi),
                                mark_segments(calls, lo, hi), mark_segments(marks, lo, hi))
    n = len(planes)
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": window_s,
        "planes": sorted(planes),
        "device_ops": [[k, v / n / 1e9] for k, v in by_op.most_common(10)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in idle_by.most_common(10)],
    }


def reduce_dir(trace_dir: str, window_s: float) -> dict | None:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(find(trace_dir)), window_s)
