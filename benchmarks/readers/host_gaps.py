"""What the host was doing while the device was idle: each idle nanosecond of
the traced window, given to the program's own host phases that were open at
that instant.

The device side is the xplane and nothing else: the median device's gaps
between its merged op intervals inside `xplane.window_ns`, the same window
and device `device_idle_pct` is of, so the shares of a partition of the
phases sum to it. The host side is the program's capture log
(`/debug/perf` `capture.intervals`: `[name, thread, start_ns, duration_ns]`
on the profiler's clock, zero at the stamp the program took immediately
before `start_trace`). On each thread the innermost open interval counts (a
`hydrate` inside a `traverser` inside a `request` is `hydrate`; what is left
of an outer interval is its self time), and an instant with phases open on
several threads is split equally over those threads.

Params: `what`:

  phases   idle time given to the intervals named in `phases`
  other    idle time given to an open interval NOT named in `phases`
  none     idle time with no interval open on any thread

-> percent of the traced window; None without a trace or without a capture
record (a program from before the capture log, or a CPU run).
"""

from bisect import bisect_right

from benchmarks.lib import xplane


def flatten(intervals):
    """One thread's (start, end, name) intervals, nested or not -> sorted,
    disjoint (start, end, name) segments, each named by the innermost
    interval open in it."""
    out, stack, cur = [], [], 0
    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > cur:
                out.append((cur, end, top))
                cur = end
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        cur = max(cur, s)
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        if end > cur:
            out.append((cur, end, top))
            cur = end
    return out


def timeline(intervals):
    """Capture intervals -> (edges, states): between edges[i] and
    edges[i + 1] the threads with a phase open hold the names states[i]
    (one entry per such thread)."""
    by_thread: dict = {}
    for name, tid, start, dur in intervals:
        if dur > 0:
            by_thread.setdefault(tid, []).append((start, start + dur, name))
    events = []
    for segs in by_thread.values():
        for s, e, name in flatten(segs):
            events.append((s, 1, name))
            events.append((e, 0, name))
    events.sort(key=lambda x: (x[0], x[1]))      # closes before opens
    edges, states, open_now = [], [], []
    for t, opens, name in events:
        if not edges or t != edges[-1]:
            if edges:
                states.append(tuple(open_now))
            edges.append(t)
        if opens:
            open_now.append(name)
        else:
            open_now.remove(name)
    return edges, states


def attribute(gaps, intervals):
    """{name: ns, None: ns}: every nanosecond of `gaps` (sorted, disjoint
    (start, end)) given to the phases open in it, None where none is."""
    edges, states = timeline(intervals)
    out: dict = {}

    def give(name, ns):
        out[name] = out.get(name, 0.0) + ns

    for g0, g1 in gaps:
        i = bisect_right(edges, g0) - 1
        t = g0
        while t < g1:
            nxt = edges[i + 1] if i + 1 < len(edges) else g1
            upto = min(max(nxt, t), g1)
            names = states[i] if 0 <= i < len(states) else ()
            if upto > t:
                if names:
                    for name in names:
                        give(name, (upto - t) / len(names))
                else:
                    give(None, upto - t)
            t = upto
            i += 1
    return out


def idle_gaps(trace):
    """(window start, window end, the median device's idle gaps inside it)."""
    w0, w1 = xplane.window_ns(trace)
    devs = xplane.device_summary(trace)["devices"]
    picked = xplane.median_device({p: d["idle_pct"] for p, d in devs.items()})
    if picked is None or w1 <= w0:
        return w0, w1, []
    busy = xplane.merge_intervals(trace[picked[0]].get(xplane.OPS_LINE, ()))
    edges = [w0] + [x for se in busy for x in se] + [w1]
    return w0, w1, [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]


def read(sources, what, phases=()):
    trace = sources.get("xplane")
    capture = (sources.get("perf") or {}).get("capture")
    if not trace or not capture:
        return None
    key = "host_gaps"      # one attribution for the five metrics
    if key not in sources:
        w0, w1, gaps = idle_gaps(trace)
        given = attribute(gaps, capture["intervals"])
        sources[key] = (w1 - w0, given)
        if w1 > w0:     # every phase's share, for the run's record (`notes`)
            sources.setdefault("notes", {})["idle_pct_by_phase"] = {
                str(name): 100.0 * ns / (w1 - w0) for name, ns in sorted(
                    given.items(), key=lambda kv: -kv[1])}
    window, given = sources[key]
    if window <= 0:
        return None
    if what == "phases":
        ns = sum(given.get(p, 0.0) for p in phases)
    elif what == "other":
        ns = sum(v for name, v in given.items()
                 if name is not None and name not in phases)
    elif what == "none":
        ns = given.get(None, 0.0)
    else:
        raise ValueError(f"host_gaps: what={what!r}")
    return 100.0 * ns / window
