"""A number the benchmark's own client side took: the generator's clock
(lateness, offered rate, request tails) and the compile cache's file count.
Param: `key`."""


def read(sources, key):
    value = (sources.get("client") or {}).get(key)
    return None if value is None else float(value)
