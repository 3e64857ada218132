"""A phase of the program's host-side dispatch ledger (/debug/perf
`phases`, filled while TRACING_ENABLED): params `phase`, `stat`."""


def read(sources, phase, stat="p50_ms"):
    ph = (sources.get("perf") or {}).get("phases", {}).get(phase)
    if not ph or ph.get("samples", 0) == 0:
        return None
    return float(ph[stat])
