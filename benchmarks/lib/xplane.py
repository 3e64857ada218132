"""From a profiler trace (`*.xplane.pb`) to device numbers.

`jax.profiler.ProfileData` reads the file with nothing but JAX (no backend is
initialised). A trace is planes; a device's plane (`/device:TPU:<n>`) has
lines, of which `XLA Ops` holds one event per operation that ran on the chip
and `XLA Modules` one per whole compiled program. All times here come from
those events and from nothing else.

    busy   = union of the op intervals of one device
    window = first op start to last op end over all devices of the trace
    idle   = 1 - busy / window
    a program's time = the durations of its `XLA Modules` events

The reduction works on plain tuples so that a test can feed it a recorded
trace cut to a few hundred events (`to_json` / `from_json`).
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    """The newest .xplane.pb under a directory the profiler wrote."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str, lines=(OPS_LINE, MODULES_LINE)) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns), ...]}}
    for the device planes, keeping only `lines`."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        kept = {}
        for line in plane.lines:
            if line.name in lines:
                kept[line.name] = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events]
        out[plane.name] = kept
    return out


def to_json(trace: dict) -> str:
    return json.dumps(trace)


def from_json(text: str) -> dict:
    return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in json.loads(text).items()}


def merge_intervals(events) -> list[tuple[int, int]]:
    """Sorted, disjoint [start, end) intervals covering every event."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: list[list[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_ns(trace: dict) -> tuple[int, int]:
    """(start, end) of the traced window: first op start to last op end over
    every device."""
    starts, ends = [], []
    for lines in trace.values():
        for _, s, d in lines.get(OPS_LINE, ()):
            if d > 0:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        return (0, 0)
    return (min(starts), max(ends))


def device_summary(trace: dict) -> dict:
    """Per device: busy seconds, idle share, op totals, the longest gaps."""
    w0, w1 = window_ns(trace)
    window = max(w1 - w0, 0)
    devices = {}
    for plane, lines in sorted(trace.items()):
        ops = lines.get(OPS_LINE, [])
        merged = merge_intervals(ops)
        busy = sum(e - s for s, e in merged)
        per_op: dict[str, int] = {}
        for name, _, d in ops:
            per_op[name] = per_op.get(name, 0) + d
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]), reverse=True)
        devices[plane] = {
            "busy_s": busy / 1e9,
            "idle_pct": 100.0 * (1.0 - busy / window) if window else None,
            "op_events": len(ops),
            "op_seconds": {k: v / 1e9 for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])},
            "gaps_s": [(g / 1e9, (at - w0) / 1e9) for g, at in gaps[:10]],
        }
    return {"window_s": window / 1e9, "devices": devices}


def module_times(trace: dict, pattern: str) -> dict:
    """Per device, the `XLA Modules` events whose name matches `pattern`:
    {plane: {module name: [durations in seconds]}}."""
    rx = re.compile(pattern)
    out = {}
    for plane, lines in sorted(trace.items()):
        by_name: dict[str, list[float]] = {}
        for name, _, d in lines.get(MODULES_LINE, ()):
            if rx.search(name):
                by_name.setdefault(name, []).append(d / 1e9)
        out[plane] = by_name
    return out


def median_device(values: dict):
    """The device whose value is the median one (the lower of two middles),
    as (plane, value); None for no devices."""
    items = sorted((v, p) for p, v in values.items() if v is not None)
    if not items:
        return None
    v, p = items[(len(items) - 1) // 2]
    return p, v
