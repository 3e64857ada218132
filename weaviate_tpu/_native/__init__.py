"""The native libraries (``native/*.cpp`` -> ``lib*.so`` in this directory).

The ``.so`` files are git-ignored build outputs, so a checkout builds them on
first use — and rebuilds one whenever it is older than its source, so a
library left over from an older ``native/*.cpp`` is never loaded as is.
``STATUS`` records what happened to each library in this process; ``/v1/meta``
serves it, so a build that was attempted and failed (three of the four
loaders then serve from their Python twin) is visible instead of silent.
"""

from __future__ import annotations

import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_DIR)), "native")

# library -> (source file, extra compiler flags)
_LIBS = {
    "hnsw": ("hnsw.cpp", ("-fopenmp",)),
    "reply": ("reply.cpp", ()),
    "lsmget": ("lsm_get.cpp", ()),
    "rescore": ("rescore.cpp", ("-pthread",)),
}

# library -> "loaded" (up to date on disk) | "built" (compiled by this
# process) | "build_failed: ..." ; absent = never requested
STATUS: dict[str, str] = {}
_lock = threading.Lock()


def ensure_built(name: str) -> str:
    """-> path of ``lib<name>.so``, compiled first when it is missing or
    older than its source. Raises (and records ``build_failed``) when the
    compile fails or the source is gone with no library to fall back on."""
    src_file, flags = _LIBS[name]
    so = os.path.join(_DIR, f"lib{name}.so")
    src = os.path.join(_SRC_DIR, src_file)
    with _lock:
        have_so, have_src = os.path.exists(so), os.path.exists(src)
        if have_so and (not have_src
                        or os.path.getmtime(so) >= os.path.getmtime(src)):
            STATUS.setdefault(name, "loaded")
            return so
        try:
            if not have_src:
                raise FileNotFoundError(f"native source not found at {src}")
            # compile beside the target and rename: a concurrent process
            # must never dlopen a half-written library
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-O3", "-march=native", "-std=c++17", *flags,
                     "-shared", "-fPIC", "-o", tmp, src],
                    check=True, capture_output=True, text=True)
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = (getattr(e, "stderr", "") or str(e)).strip()
            STATUS[name] = f"build_failed: {detail[-400:]}"
            raise
        STATUS[name] = "built"
        return so
