"""`write_roofline`: the least time the chip needs for the rows ONE
execution of the write program was given, over that execution's device
time.

The work is the same whatever implements the write: each row's components
written to the slab once, rows x dim x 4 B over the chip's peak bytes/s
(lib/costs.py). A program that copies the slab to write 100 rows reads a
small share; one that writes in place reads what its launch costs leave.
Nothing here is a constant of the cell: the rows an execution was given are
/debug/perf `writes.rows / writes.batches` (what the server's write path
counted in its window), the width is the configuration's, and the time is
the median `XLA Modules` event of the program `module` (a regex) matches
with most device time, on the median device (readers/xplane_ops.py's rule).
A program without the `writes` account (one from before it), or a window
without a write, gives no value; `notes` carries what was used.

Param: `module`.
"""

from benchmarks.lib import costs, stats, xplane


def read(sources, module):
    trace = sources.get("xplane")
    writes = (sources.get("perf") or {}).get("writes") or {}
    if not trace or not writes.get("batches") or not writes.get("rows"):
        return None
    per_dev, names = {}, {}
    for plane, by_name in xplane.module_times(trace, module).items():
        if by_name:
            name = max(by_name, key=lambda n: sum(by_name[n]))
            per_dev[plane] = stats.median(by_name[name])
            names[plane] = name
    picked = xplane.median_device(per_dev)
    if picked is None:
        return None
    plane, seconds = picked
    cell = sources["cell"]
    rows = float(writes["rows"]) / float(writes["batches"])
    share, bound = costs.roofline_share(
        0.0, costs.scan_bytes(rows, cell["dim"], 4), seconds,
        cell["device_kind"])
    sources.setdefault("notes", {}).update({
        "write_roofline_program": names[plane],
        "write_roofline_rows": rows,
        "write_roofline_program_ms": seconds * 1e3,
        "write_roofline_bound": bound})
    return share
