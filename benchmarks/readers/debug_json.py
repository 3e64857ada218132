"""A number out of one of the server's JSON pages collected after the
window. Params: `page` (perf | debug_memory | debug_index | meta), `path`
(keys from the top), optional `over` (a second path on the same page to
divide by), `over_peak` (a key of the chip's row in lib/costs.PEAKS to divide
by), `scale`."""

from benchmarks.lib import costs


def _dig(doc, path):
    for key in path:
        if isinstance(doc, dict) and key in doc:
            doc = doc[key]
        else:
            return None
    return doc


def read(sources, page, path, over=None, over_peak=None, scale=1.0):
    value = _dig(sources.get(page), path)
    if value is None:
        return None
    value = float(value)
    if over is not None:
        denom = _dig(sources.get(page), over)
        if not denom:
            return None
        value /= float(denom)
    if over_peak is not None:
        value /= float(costs.peaks(sources["cell"]["device_kind"])[over_peak])
    return value * float(scale)
