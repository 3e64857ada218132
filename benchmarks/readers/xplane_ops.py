"""Device numbers from the profiler trace the server wrote during the window
(`GET /debug/pprof/trace`), reduced by benchmarks/lib/xplane.py. Every time
here comes from the trace and from nothing else. Params: `what`:

  idle_pct      100 x (1 - union of op intervals / traced window); several
                devices: the median one
  module_ms     median duration of one execution of the search program: the
                `XLA Modules` events matching `module` (a regex); where it
                matches several programs, the one with most total time
  roofline_pct  the least time the chip could take for what ONE execution of
                that program was given, over module_ms. The least time is
                the larger of 2 x queries x rows scanned x dim over peak
                FLOP/s (nothing for a scan of codes) and rows scanned x
                bytes a row over peak bytes/s (lib/costs.py). What an execution was given is read from the
                server's own pages and held to the configuration:

                queries       /debug/perf `rows / dispatches`: what a
                              dispatch carried, not the traffic's width
                tier          /debug/perf `tiers`; where several tiers
                              served the window, the one whose `module`
                              (param `tiers`) matches the program's name
                operand       the slab that tier scans (param `tiers`), which
                              /debug/memory's device components must hold
                bytes a row   that component's bytes over /debug/index's
                              capacity: 4, 2 or 1 B a component of the
                              configuration's `dim`, or, for codes, the
                              configuration's `pq.segments`
                rows scanned  the configuration's rows a chip (/debug/index
                              `live` must be the configuration's rows),
                              unless /debug/perf `tier_rows` (rows the tier's
                              dispatches scanned, all chips together) says
                              fewer; a tier that scans a part (`part` in
                              `tiers`) has no reading without that account

                An account that is missing, or that disagrees with the
                configuration, gives no value (never a guess, never a
                constant) and the note `roofline_null` says which. The
                notes carry what was used: `roofline_operand`,
                `roofline_bytes_a_row`, `roofline_rows`, `roofline_queries`,
                `roofline_tier`, `roofline_program`, `roofline_bound`.
"""

import re

from benchmarks.lib import costs, stats, xplane


def _module(trace, module):
    """(median ms of one execution, the program's name) on the median
    device, or None."""
    per_dev, names = {}, {}
    for plane, by_name in xplane.module_times(trace, module).items():
        if by_name:
            name = max(by_name, key=lambda n: sum(by_name[n]))
            per_dev[plane] = stats.median(by_name[name]) * 1e3
            names[plane] = name
    picked = xplane.median_device(per_dev)
    return None if picked is None else (picked[1], names[picked[0]])


class _NoReading(Exception):
    pass


def _given(sources, program, tiers):
    """What one execution of `program` was given: (tier, operand, queries,
    rows scanned a chip, bytes a row, components a row that are multiplied)."""
    cell = sources["cell"]
    perf = sources.get("perf") or {}
    served = {t: n for t, n in (perf.get("tiers") or {}).items() if n}
    if not served or not perf.get("dispatches"):
        raise _NoReading("/debug/perf counts no dispatch in its window")
    match = [t for t in served if t in tiers and (
        len(served) == 1
        or re.search(tiers[t].get("module", "$^"), program))]
    if len(match) != 1:
        raise _NoReading(
            f"tiers {served} served the window and the traced program "
            f"{program} belongs to {match or 'none the benchmark knows'}")
    tier = match[0]
    queries = float(perf["rows"]) / float(perf["dispatches"])
    index = sources.get("index") or {}
    capacity, live = index.get("capacity"), index.get("live")
    if not capacity or live != cell["rows"]:
        raise _NoReading(f"/debug/index: capacity {capacity}, live {live}, "
                         f"the configuration has {cell['rows']} rows")
    operand = tiers[tier]["operand"]
    held = ((sources.get("debug_memory") or {}).get("device") or {}).get(
        "components") or {}
    if not held.get(operand):
        raise _NoReading(f"tier {tier} scans {operand}, which /debug/memory "
                         f"does not hold on the device ({sorted(held)})")
    bytes_a_row = held[operand] / capacity
    if bytes_a_row / cell["dim"] in (4.0, 2.0, 1.0):
        width = cell["dim"]
    elif cell.get("pq_segments") and bytes_a_row == cell["pq_segments"]:
        # codes: a table look-up and an add a segment, work that no peak of
        # costs.PEAKS measures; the bytes alone bound such a scan
        width = 0
    else:
        raise _NoReading(
            f"{operand} holds {bytes_a_row:g} B a row of capacity: not 4, 2 "
            f"or 1 B a component of dim {cell['dim']}, nor the "
            f"configuration's pq.segments ({cell.get('pq_segments')})")
    rows = cell["rows"] / cell["chips"]
    scanned = (perf.get("tier_rows") or {}).get(tier)
    if scanned is not None:
        scanned = float(scanned) / served[tier] / cell["chips"]
        if scanned > rows * 1.0001:
            raise _NoReading(f"/debug/perf tier_rows says {scanned:g} rows a "
                             f"dispatch a chip, the configuration has {rows:g}")
        rows = scanned
    elif tiers[tier].get("part"):
        raise _NoReading(f"tier {tier} scans a part of the rows and "
                         "/debug/perf has no tier_rows to say how many")
    return tier, operand, queries, rows, bytes_a_row, width


def read(sources, what, module=None, tiers=None):
    trace = sources.get("xplane")
    if not trace:
        return None
    if what == "idle_pct":
        devs = xplane.device_summary(trace)["devices"]
        picked = xplane.median_device(
            {p: d["idle_pct"] for p, d in devs.items()})
        return None if picked is None else picked[1]
    if what == "module_ms":
        found = _module(trace, module)
        return None if found is None else found[0]
    if what == "roofline_pct":
        found = _module(trace, module)
        if found is None:
            return None
        ms, program = found
        notes = sources.setdefault("notes", {})
        notes["roofline_program"] = program
        try:
            tier, operand, queries, rows, bytes_a_row, width = _given(
                sources, program, tiers or {})
        except _NoReading as e:
            notes["roofline_null"] = str(e)
            return None
        share, bound = costs.roofline_share(
            costs.scan_flops(queries, rows, width),
            costs.scan_bytes(rows, 1, bytes_a_row), ms / 1e3,
            sources["cell"]["device_kind"])
        notes.update({
            "roofline_tier": tier, "roofline_operand": operand,
            "roofline_bytes_a_row": bytes_a_row, "roofline_rows": rows,
            "roofline_queries": queries, "roofline_bound": bound})
        return share
    raise ValueError(f"xplane_ops: what={what!r}")
