"""A Prometheus sample of the server's /metrics after the window. Params:
`sample` (its full name), optional `labels` (all must match), `reduce`
(sum | max over the matching samples)."""


def read(sources, sample, labels=None, reduce="sum"):
    vals = [v for name, lab, v in sources.get("prom") or ()
            if name == sample
            and all(lab.get(k) == w for k, w in (labels or {}).items())]
    if not vals:
        return None
    return float(sum(vals) if reduce == "sum" else max(vals))
