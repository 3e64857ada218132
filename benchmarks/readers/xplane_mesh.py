"""What exists only across chips, from the device planes of the profiler
trace the server wrote during the window (`GET /debug/pprof/trace`), reduced
by benchmarks/lib/xplane.py. Nothing here comes from the program's own
estimates, and no FLOPs or bytes are reckoned: both numbers are times the
trace holds. Params: `what`:

  collective_ms    device time of the collective ops inside one execution of
                   the search program. The program is the `XLA Modules`
                   events matching `module` (a regex; where it matches
                   several programs, the one with most total time, as
                   xplane_ops picks it); a collective is an `XLA Ops` event
                   whose instruction is an all-gather, all-reduce or
                   collective-permute (the `-start` and `-done` halves of an
                   asynchronous one too). Per execution: the union of the
                   collectives' intervals that begin inside it; then the
                   median over executions, then the median device. None
                   where no execution holds a collective (one chip).
  busy_spread_pct  highest less lowest busy share of the traced window over
                   the device planes, in points: 0 on a balanced mesh and on
                   one chip. `notes.busy_pct_by_device` has the shares.

A collective's device time is the transfer and the wait for the chips that
have not arrived: with a payload of kilobytes it is mostly the wait.
"""

import re
from bisect import bisect_left, bisect_right

from benchmarks.lib import stats, xplane

COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|collective-permute)(-start|-done)?\b")


def _collective_ms(trace, module):
    per_dev, seen = {}, {}
    rx = re.compile(module)
    for plane, lines in sorted(trace.items()):
        by_name: dict = {}
        for name, start, dur in lines.get(xplane.MODULES_LINE, ()):
            if dur > 0 and rx.search(name):
                by_name.setdefault(name, []).append((start, start + dur))
        if not by_name:
            continue
        runs = by_name[max(by_name, key=lambda n: sum(
            e - s for s, e in by_name[n]))]
        coll = sorted((ev for ev in lines.get(xplane.OPS_LINE, ())
                       if ev[2] > 0 and COLLECTIVE.match(ev[0])),
                      key=lambda ev: ev[1])
        starts = [ev[1] for ev in coll]
        per_run = []
        for s, e in runs:
            inside = coll[bisect_left(starts, s):bisect_right(starts, e - 1)]
            if inside:
                per_run.append(sum(
                    b - a for a, b in xplane.merge_intervals(inside)) / 1e6)
        if per_run:
            per_dev[plane] = stats.median(per_run)
            seen[plane] = {"executions": len(per_run), "ms": per_dev[plane]}
    picked = xplane.median_device(per_dev)
    return (None, seen) if picked is None else (picked[1], seen)


def read(sources, what, module=None):
    trace = sources.get("xplane")
    if not trace:
        return None
    notes = sources.setdefault("notes", {})
    if what == "collective_ms":
        value, seen = _collective_ms(trace, module)
        if value is not None:
            notes["collective_ms"] = {
                "by_device": seen,
                "reads": "the union of all-gather / all-reduce / "
                         "collective-permute op intervals inside one "
                         "execution: the payload is kilobytes a chip, so "
                         "this is mostly the wait for the slowest chip"}
        return value
    if what == "busy_spread_pct":
        devs = xplane.device_summary(trace)["devices"]
        busy = {p: 100.0 - d["idle_pct"] for p, d in devs.items()
                if d["idle_pct"] is not None}
        if not busy:
            return None
        notes["busy_pct_by_device"] = busy
        return max(busy.values()) - min(busy.values())
    raise ValueError(f"xplane_mesh: what={what!r}")
