"""What the process runs on: device identity, the Pallas execution mode and
the persistent compile cache.

One module so that no caller decides any of the three for itself: the
start-up line, ``/v1/meta``, the cost model's peaks table and
``chip_smoke.py`` all read ``identity()``; every ``pallas_call`` site takes
its ``interpret`` flag from ``pallas_interpret()``; every entry point
(``python -m weaviate_tpu``, ``__graft_entry__``) places the
compile cache with ``enable_compile_cache()`` before first backend use.

Importing this module does not import jax; calling ``identity()`` or
``pallas_interpret()`` initialises the backend (and raises when none
comes up — a process that was meant to own a chip must not serve on
whatever it got).
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def identity() -> dict:
    """{"platform", "device_kind", "count"} as JAX reports the live
    backend (``jax.devices()[0].platform``, ``.device_kind``,
    ``len(jax.devices())``)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


def pallas_interpret() -> bool:
    """The ``interpret`` flag of every Pallas kernel: compiled by Mosaic on
    ``tpu``, interpreted on ``cpu`` (the test tier). Any other backend is
    an error — a kernel quietly interpreted on a platform nobody chose
    would report answers at a speed nobody deploys."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the live JAX backend is {backend!r}")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before first backend
    use. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is
    touched. Otherwise ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what makes a later process find the entries.
    -> the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile, not only those over JAX's one-second default: a
    # second process then finds ALL of the first one's programs, and a
    # compile that takes 0.9 s one run and 1.1 s the next writes nothing new
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
