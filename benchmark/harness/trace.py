"""Reduction of a jax.profiler ``.xplane.pb`` to intervals, and of intervals to
the numbers the per-layer readers and the result line's ``device`` need.

On a TPU the device's operations are the events of line "XLA Ops" of each plane
"/device:TPU:<n>". On the CPU backend (``--tiny`` rehearsals and the tests) they
are the events of the "tf_XLAPjRtCpuClient/*" lines of plane "/host:CPU". Host
spans (``jax.profiler.TraceAnnotation`` and JAX's own TraceMes) are every other
event of "/host:CPU". Times are seconds on the trace's own clock.
"""

import glob
import os
import re
from collections import defaultdict

_HOST_NOISE = ("ThreadpoolListener", "SlinkyThreadPool", "$")
_CONTAINERS = re.compile(r"^(while|conditional|call)([.\d_]|$)")


def _plain(name: str) -> str:
    """An op or span name as a ledger can carry it: letters, digits, _ . -
    A device op's name is its HLO text; its instruction name and output type stay."""
    if " = " in name:
        op, rest = name.split(" = ", 1)
        name = op + ":" + rest.split("{", 1)[0].split(" ", 1)[0]
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name).strip("_")[:64]


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, platform: str) -> dict:
    """{"device": {plane: [(name, start_s, end_s)]}, "host": [(name, start_s, end_s)]}"""
    from jax.profiler import ProfileData

    device, host = defaultdict(list), []
    for plane in ProfileData.from_file(path).planes:
        on_tpu = plane.name.startswith("/device:TPU:")
        if not (on_tpu or plane.name == "/host:CPU"):
            continue
        for line in plane.lines:
            if on_tpu:
                if line.name != "XLA Ops":
                    continue
                sink = device[plane.name]
            elif platform != "tpu" and line.name.startswith("tf_XLAPjRtCpuClient"):
                sink = device["/host:CPU"]
            elif not on_tpu:
                sink = host
            for ev in line.events:
                if ev.duration_ns <= 0 or ev.name.startswith(_HOST_NOISE):
                    continue
                s = ev.start_ns * 1e-9
                sink.append((ev.name.lstrip("%"), s, s + ev.duration_ns * 1e-9))
    return {"device": dict(device), "host": host}


def clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def union(events):
    """Merged [start, end] intervals, sorted."""
    out = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(events) -> float:
    return sum(e - s for s, e in union(events))


def self_times(events) -> dict:
    """Seconds by op name, counting each instant once: a container (a while loop
    and the ops of its body sit on one line) keeps only what its children leave."""
    total = defaultdict(float)
    stack = []  # [name, end, children_seconds, start]

    def pop():
        name, end, kids, start = stack.pop()
        total[name] += max(end - start - kids, 0.0)
        if stack:
            stack[-1][2] += end - start

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        # an op is a child only of an op that holds it whole
        while stack and (stack[-1][1] <= s or e > stack[-1][1]):
            pop()
        stack.append([name, e, 0.0, s])
    while stack:
        pop()
    return dict(total)


def pattern_seconds(events, pattern: str) -> float:
    """Union of the time in ops whose name matches ``pattern`` (containers out)."""
    rx = re.compile(pattern)
    return busy_seconds([ev for ev in events
                         if rx.search(ev[0]) and not _CONTAINERS.match(ev[0])])


def gaps(events, lo: float, hi: float):
    """Idle intervals of [lo, hi]: what the union of ``events`` leaves."""
    out, at = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def attribute_gaps(idle, host_events, top: int = 10):
    """Idle seconds by the host span that covered each gap's midpoint: the
    shortest such span, "no_host_span" where none did."""
    by = defaultdict(float)
    for s, e in idle:
        mid = 0.5 * (s + e)
        cover = [(he - hs, n) for n, hs, he in host_events if hs <= mid <= he]
        by[min(cover)[1] if cover else "no_host_span"] += e - s
    return sorted(by.items(), key=lambda kv: -kv[1])[:top]


def span_bounds(host_events, name: str):
    """[start, end] of the one host span with this name (the traced slice)."""
    hits = [(s, e) for n, s, e in host_events if n == name]
    if not hits:
        raise RuntimeError(f"trace holds no span {name!r}")
    return hits[0]


def reduce_slice(trace: dict, slice_name: str) -> dict:
    """Everything the readers need, clipped to the span ``slice_name``."""
    lo, hi = span_bounds(trace["host"], slice_name)
    planes = {p: clip(evs, lo, hi) for p, evs in trace["device"].items()}
    planes = {p: evs for p, evs in planes.items() if evs}
    if not planes:
        raise RuntimeError("no operation ran on a device inside the traced slice")
    busy = [busy_seconds(evs) for evs in planes.values()]
    first = next(iter(planes.values()))
    ops = self_times(first)
    top_ops = sorted(((n, t) for n, t in ops.items() if not _CONTAINERS.match(n)),
                     key=lambda kv: -kv[1])[:10]
    host = [ev for ev in clip(trace["host"], lo, hi) if ev[0] != slice_name]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "planes": planes,
        "device_ops": [[_plain(n), t] for n, t in top_ops],
        "idle_gaps": [[_plain(n), t] for n, t in attribute_gaps(gaps(first, lo, hi), host)],
    }
