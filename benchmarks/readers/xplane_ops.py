"""Device numbers from the profiler trace the server wrote during the window
(`GET /debug/pprof/trace`), reduced by benchmarks/lib/xplane.py. Nothing here
comes from the program's own estimates. Params: `what`:

  idle_pct      100 x (1 - union of op intervals / traced window); several
                devices: the median one
  module_ms     median duration of one execution of the search program: the
                `XLA Modules` events matching `module` (a regex); where it
                matches several programs, the one with most total time
  roofline_pct  the least time the chip could take for one scan of the
                cell's shape (the larger of FLOPs over peak FLOP/s and bytes
                over peak bytes/s, lib/costs.py) over module_ms
"""

from benchmarks.lib import costs, stats, xplane


def _module_ms(trace, module):
    per_dev = {}
    for plane, by_name in xplane.module_times(trace, module).items():
        if by_name:
            name = max(by_name, key=lambda n: sum(by_name[n]))
            per_dev[plane] = stats.median(by_name[name]) * 1e3
    picked = xplane.median_device(per_dev)
    return None if picked is None else picked[1]


def read(sources, what, module=None):
    trace = sources.get("xplane")
    if not trace:
        return None
    if what == "idle_pct":
        devs = xplane.device_summary(trace)["devices"]
        picked = xplane.median_device(
            {p: d["idle_pct"] for p, d in devs.items()})
        return None if picked is None else picked[1]
    if what == "module_ms":
        return _module_ms(trace, module)
    if what == "roofline_pct":
        ms = _module_ms(trace, module)
        if ms is None:
            return None
        cell = sources["cell"]
        per_chip_rows = cell["rows"] / cell["chips"]
        share, bound = costs.roofline_share(
            costs.scan_flops(cell["batch"], per_chip_rows, cell["dim"]),
            costs.scan_bytes(per_chip_rows, cell["dim"]),
            ms / 1e3, cell["device_kind"])
        sources.setdefault("notes", {})["roofline_bound"] = bound
        return share
    raise ValueError(f"xplane_ops: what={what!r}")
