"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
device operations that took the most time, and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<n>``; on each, the line of XLA operations (``XLA Ops``)
holds one event per operation run.  Busy time is the union of those
intervals inside the traced window, averaged over the devices; idle
gaps are the holes in that union, each labelled by the benchmark's own
host span (``bench.*``, a ``jax.profiler.TraceAnnotation``) that
overlaps it most.  The window is the host span ``bench.traced_window``
when the trace holds it, else the first to the last device event.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(root: str) -> str:
    """The newest ``*.xplane.pb`` under ``root`` (or ``root`` itself)."""
    if os.path.isfile(root):
        return root
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(found, key=os.path.getmtime)


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


_OP_KIND = re.compile(r"\s([a-z][\w-]*)\(")


def _event_name(ev) -> str:
    """A device event's name: a TPU names it by the HLO instruction's
    text, shortened here to its name and kind (``%fusion.3 fusion``);
    prefixed by its module (``<module>/``) where the event names one."""
    name = ev.name
    lhs, eq, rhs = name.partition(" = ")
    if eq:
        kind = _OP_KIND.search(rhs)
        name = f"{lhs} {kind.group(1)}" if kind else lhs
    module = None
    for key, value in ev.stats:
        if key == "hlo_module":
            module = value
            break
    return f"{module}/{name}" if module else name


def reduce(path: str) -> dict:
    """:func:`reduce_data` of the trace file at (or under) ``path``; a
    file ending in ``.gz`` is read through gzip."""
    from jax.profiler import ProfileData
    path = find_xplane(path)
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return reduce_data(ProfileData.from_serialized_xspace(f.read()))
    return reduce_data(ProfileData.from_file(path))


def reduce_data(data) -> dict:
    """``busy_s`` and ``window_s`` (seconds), ``devices`` (count),
    ``device_ops`` and ``idle_gaps`` (lists of [name, seconds], longest
    first, at most ten each) and ``spans`` (host span name -> seconds
    inside the window) of a ``jax.profiler.ProfileData``."""
    device_events = collections.defaultdict(list)   # plane -> [(s, e, name)]
    spans = []                                      # (s, e, name)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns
                    device_events[plane.name].append(
                        (s, s + ev.duration_ns, _event_name(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns
                        spans.append((s, s + ev.duration_ns, ev.name))
    if not device_events:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OPS_LINE!r} "
                         f"line in the trace")
    window = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if window:
        w0, w1 = window[0]
    else:
        w0 = min(s for evs in device_events.values() for s, _, _ in evs)
        w1 = max(e for evs in device_events.values() for _, e, _ in evs)
    labelled = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    per_op = collections.Counter()
    gaps = []
    busy = 0.0
    for evs in device_events.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            per_op[n] += e - s
        merged = _union((s, e) for s, e, _ in clipped)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    n_dev = len(device_events)
    labels = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best = max(labelled, key=lambda sp: _overlap(g0, g1, sp[0], sp[1]),
                   default=None)
        name = (best[2] if best is not None
                and _overlap(g0, g1, best[0], best[1]) > 0
                else "no bench span")
        labels.append([name, (g1 - g0) / 1e9])
    span_time = collections.Counter()
    for s, e, n in labelled:
        span_time[n] += _overlap(s, e, w0, w1) / 1e9
    return {
        "busy_s": busy / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": n_dev,
        "device_ops": [[n, t / n_dev / 1e9]
                       for n, t in per_op.most_common(TOP)],
        "idle_gaps": labels,
        "spans": dict(span_time),
    }
