"""Self time of a span in the program's request traces (/debug/traces, the
ring's last requests): the root span's duration less what its children
cover. Params: `kind` (grpc | rest), `names` (request names to keep, e.g.
Search, BatchSearch), `stat` (p50)."""

from benchmarks.lib import stats


def self_ms(span: dict) -> float:
    return float(span["duration_ms"]) - sum(
        float(c["duration_ms"]) for c in span.get("children", ()))


def read(sources, kind="grpc", names=None, since_key="window_start_unix_ms"):
    doc = sources.get("traces") or {}
    since = (sources.get("client") or {}).get(since_key, 0.0)
    vals = [self_ms(t["root"]) for t in doc.get("traces", ())
            if t.get("kind") == kind and t.get("root")
            and (not names or t.get("name") in names)
            and t.get("start_unix_ms", 0.0) >= since]
    if not vals:
        return None
    return stats.median(vals)
