"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The reduction works on plain tuples, so that it can be tested without a
chip:

  * device ops: ``(name, start_ns, duration_ns, device)`` from the "XLA Ops"
    line of every ``/device:TPU:<k>`` plane. On the TPU an op's name is its
    HLO instruction text (``%fusion.343 = f32[192] fusion(...), kind=...``)
    and control flow nests: a ``while`` or ``conditional`` event spans the
    ops it runs;
  * host spans: ``(name, start_ns, duration_ns)`` of the benchmark's own
    ``bench.*`` annotations, from the host plane.

The traced window is the ``bench.window`` span. Within it:

  * busy time is the union of one device's op intervals, averaged over the
    devices; idle is the rest of the window;
  * device time of the leaf ops (not control flow) is summed by op and by
    class (``CLASSES``);
  * each idle gap is attributed to the innermost ``bench.*`` span under it
    (the one that started last), or to ``(no span)``.
"""

from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_SPAN = "bench.window"

CONTROL_FLOW = ("while", "conditional", "call")


def opcode(name: str) -> str:
    """The HLO opcode of an op's instruction text (the name itself when it
    is not instruction text)."""
    m = re.match(r"%?[\w.\-]+ = .*? ([a-z][a-z0-9_\-]*)\(", name)
    return m.group(1) if m else name


def short(name: str) -> str:
    """``%fusion.343 = f32[192]{..} fusion(..), kind=kCustom`` ->
    ``fusion.343 f32[192] kCustom``: the op, its result and its kind."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    kind = re.search(r"kind=(k\w+)|custom_call_target=\"(\w+)\"", rhs)
    label = [lhs.lstrip("%"), rhs.split("{", 1)[0].split(" ", 1)[0][:48]]
    if kind:
        label.append(kind.group(1) or kind.group(2))
    return " ".join(label)


def _index_op(s: str) -> bool:
    # XLA's gathers, scatters and dynamic-update-slices; on the TPU a gather
    # or scatter is a custom fusion that takes s32 indices.
    op = opcode(s)
    return (op in ("gather", "scatter", "dynamic-update-slice")
            or (op == "fusion" and "kind=kCustom" in s and "s32[" in s))


#: op class -> predicate on the op's instruction text. The
#: sweep kernel is the Pallas call named ``metric_sweep``; the bucket
#: engine's XLA index ops are its gathers, scatters, dynamic-update-slices.
CLASSES = {
    "sweep": lambda s: "metric_sweep" in s,
    "gather_scatter": lambda s: "metric_sweep" not in s and _index_op(s),
}


def union(intervals):
    """Merge (start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(device_ops, host_spans, classes=CLASSES, top=10) -> dict | None:
    """The traced window's summary, or None when it has no window span or
    no device op inside it."""
    windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    window_ns = hi - lo
    by_dev = collections.defaultdict(list)
    by_name = collections.Counter()
    by_class = collections.Counter()
    for name, s, d, dev in device_ops:
        a, b = _clip(s, s + d, lo, hi)
        if b <= a:
            continue
        by_dev[dev].append((a, b))
        if opcode(name) in CONTROL_FLOW:
            continue
        by_name[short(name)] += b - a
        for cls, pred in classes.items():
            if pred(name):
                by_class[cls] += b - a
    if not by_dev:
        return None
    busy = {dev: union(iv) for dev, iv in by_dev.items()}
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values())
    busy_ns /= len(busy)
    # idle gaps of the first device, by the innermost bench span under them
    spans = sorted((s, s + d, n) for n, s, d in host_spans
                   if n != WINDOW_SPAN)
    gaps = collections.Counter()
    first = busy[sorted(busy)[0]]
    cursor = lo
    for s, e in first + [(hi, hi)]:
        if s > cursor:
            _attribute(cursor, s, spans, gaps)
        cursor = max(cursor, e)
    ndev = len(busy)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "devices": ndev,
        "op_s": {k: v * 1e-9 / ndev for k, v in by_name.items()},
        "class_s": {k: v * 1e-9 / ndev for k, v in by_class.items()},
        "device_ops": [[k, v * 1e-9 / ndev]
                       for k, v in by_name.most_common(top)],
        "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(top)],
    }


def _attribute(a, b, spans, gaps):
    """Split the gap [a, b) among the innermost spans that cover it."""
    points = sorted({a, b} | {p for s, e, _ in spans for p in (s, e)
                              if a < p < b})
    for p, q in zip(points, points[1:]):
        mid = (p + q) / 2
        inner = None
        for s, e, name in spans:
            if s <= mid < e and (inner is None or s >= inner[0]):
                inner = (s, name)
        gaps[inner[1] if inner else "(no span)"] += q - p


def from_xplane(path: str):
    """(device_ops, host_spans) of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns, ev.duration_ns, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns))
    return ops, spans


def find_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None
