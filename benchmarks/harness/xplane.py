"""From a profiler trace (``.xplane.pb``) to busy time, program times and gaps.

Read with nothing but JAX (``jax.profiler.ProfileData``). What the planes of
a v5e trace look like (looked at by hand, PERF.md section 3): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Modules`` has one event per run
of a jitted program, named ``jit_<function>(<fingerprint>)``, and whose line
``XLA Ops`` has one event per device operation; ``/host:CPU`` has one line per
host thread, on which ``jax.profiler.TraceAnnotation`` spans appear by name.

The reduction works on plain tuples so that it can be tested without a trace:
    planes = {plane name: {line name: [(event name, start_ns, duration_ns), ...]}}
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def read_planes(path) -> dict:
    """The planes of one ``.xplane.pb`` as plain tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events)
    return planes


def newest_xplane(logdir) -> Path:
    found = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def union_ns(intervals) -> int:
    """Total length covered by (start, duration) intervals, overlaps once."""
    total, cur_s, cur_e = 0, None, None
    for s, d in sorted(intervals):
        e = s + d
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, d in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


_OP_KIND = re.compile(r"\s([a-z][\w-]*)\(")


def short_op(name: str) -> str:
    """A device operation's name as the breakdown carries it: the trace names
    an operation by its whole HLO line; keep the result's name and the kind
    (``%fused_topk.1 custom-call``)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:96]
    kind = _OP_KIND.search(" " + rhs)
    return (lhs + (" " + kind.group(1) if kind else ""))[:96]


def device_planes(planes: dict) -> list:
    return sorted(n for n in planes if DEVICE_PLANE.match(n))


def window_ns(planes: dict) -> tuple:
    starts = [s for lines in planes.values() for evs in lines.values() for _, s, _ in evs]
    ends = [s + d for lines in planes.values() for evs in lines.values() for _, s, d in evs]
    if not starts:
        raise ValueError("the trace holds no event")
    return min(starts), max(ends)


def busy_seconds(planes: dict) -> float:
    """Seconds in which an operation ran on the device: the union of the
    ``XLA Ops`` intervals, averaged over the chips that ran anything."""
    per_chip = []
    for name in device_planes(planes):
        ops = planes[name].get(OPS_LINE) or planes[name].get(MODULES_LINE) or []
        if ops:
            per_chip.append(union_ns((s, d) for _, s, d in ops) / 1e9)
    if not per_chip:
        raise ValueError("no operation ran on a device in the traced window")
    return sum(per_chip) / len(per_chip)


def top_device_ops(planes: dict, n: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device time, with
    the programs (prefixed ``program:``) beside them."""
    acc = {}
    for name in device_planes(planes)[:1]:
        for ev, _, d in planes[name].get(OPS_LINE, []):
            key = short_op(ev)
            acc[key] = acc.get(key, 0) + d
        for ev, _, d in planes[name].get(MODULES_LINE, []):
            key = "program:" + program_name(ev)
            acc[key] = acc.get(key, 0) + d
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(planes: dict, n: int = 10, min_gap_ns: int = 20_000) -> list:
    """[[what the host was doing, seconds], ...] for the longest idle gaps of
    the first chip, summed by name. A gap is named by the shortest host span
    (a ``TraceAnnotation`` or a traced runtime call) that covers its middle,
    else by ``after <the span that ended last before it>`` on the program's own
    thread (any host thread where the trace names none so): the host was then
    in code the trace has no span for."""
    devs = device_planes(planes)
    if not devs:
        return []
    ops = planes[devs[0]].get(OPS_LINE) or planes[devs[0]].get(MODULES_LINE) or []
    busy = merged((s, d) for _, s, d in ops)
    t0, t1 = window_ns(planes)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] - edges[i] >= min_gap_ns]
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:2000]
    host = []
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for lname, evs in lines.items():
                host.extend((s, s + d, ev) for ev, s, d in evs if d > 0)
    host.sort()
    starts = [h[0] for h in host]
    # "after ...": the program's own thread says more than the runtime's workers
    own = [(s + d, ev) for pname, lines in planes.items() if pname.startswith("/host:")
           for lname, evs in lines.items() if lname.startswith("python") for ev, s, d in evs if d > 0]
    by_end = sorted(own) or sorted((e, ev) for _, e, ev in host)
    ends = [h[0] for h in by_end]

    acc = {}
    for gs, ge in gaps:
        mid = (gs + ge) // 2
        hi = bisect.bisect_right(starts, mid)
        name, best = None, None
        for s, e, ev in host[max(0, hi - 400):hi]:
            if e >= mid and (best is None or e - s < best):
                name, best = ev, e - s
        if name is None:
            k = bisect.bisect_left(ends, mid)
            name = f"after {by_end[k - 1][1]}" if k else "(no host span yet)"
        acc[name] = acc.get(name, 0) + (ge - gs)
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summarize(planes: dict) -> dict:
    """Everything the per-layer readers and the result line take from a trace."""
    t0, t1 = window_ns(planes)
    programs = {}
    for name in device_planes(planes)[:1]:
        for ev, _, d in planes[name].get(MODULES_LINE, []):
            p = programs.setdefault(ev, [0.0, 0])  # fingerprint kept: one name can be many programs
            p[0] += d / 1e9
            p[1] += 1
    ops = {}
    for name in device_planes(planes)[:1]:
        # An operation belongs to the program whose run (an event of the
        # modules line) its start falls into: a program can then be found by
        # what it reads as well as by its name.
        runs = sorted((s, s + d, ev) for ev, s, d in planes[name].get(MODULES_LINE, []))
        starts = [r[0] for r in runs]
        for ev, s, d in planes[name].get(OPS_LINE, []):
            k = bisect.bisect_right(starts, s) - 1
            inside = runs[k][2] if k >= 0 and s < runs[k][1] else ""
            o = ops.setdefault(ev, [0.0, 0, inside])
            o[0] += d / 1e9
            o[1] += 1
    ops = dict(sorted(ops.items(), key=lambda kv: -kv[1][0])[:300])
    return {
        "ops": ops,
        "busy_s": busy_seconds(planes),
        "window_s": (t1 - t0) / 1e9,
        "programs": programs,
        "breakdown": {"device_ops": top_device_ops(planes), "idle_gaps": idle_gaps(planes)},
    }
