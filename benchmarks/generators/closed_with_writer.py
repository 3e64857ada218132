"""The closed loop of generators/closed.py and, beside it, ONE paced writer:
a sync job that re-imports stored rows by REST while the callers search.

Parameters (traffic file), beside closed.py's: `write_batch` (objects a REST
batch), `write_rows_per_s` (the writer's rate: a batch is due every
`write_batch / write_rows_per_s` seconds from the window's start),
`distinct_writes` (the writer cycles through that many bodies, built AND
encoded from the seed before the window, so nothing is drawn or serialised
inside it). A batch is sent at its due time or when the batch before it has
returned, whichever is later: never two in flight. The writer stops at the
window's end. Each body puts `write_batch` stored rows again, distinct
within the batch and uniform over the corpus, under their own uuid, with the
properties the configuration's dataset gives them and their own vector: the
write path runs (a fresh doc id, a slot, a log record and a device write a
row, and a delete of the previous version) and the exact answers stay what
they were.

Records are the harness's: a write is `Request("write", None, no queries)`
with `t_due` its scheduled time, so a batch the writer could not send on
time shows as latency; a batch the server did not answer 200 with SUCCESS
for every object is a failed request. `sender` says how the writer kept
its schedule.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import threading
import time
import urllib.request

import numpy as np

from benchmarks.lib.bodies import encode_all
from benchmarks.lib.requests import Request


def _closed():
    """generators/closed.py, the file beside this one: its loop IS the
    readers' half, not a copy of it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "closed.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_generators_closed", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bodies(ctx, count: int) -> list[bytes]:
    """`count` encoded REST batches from the seed's writer stream (the
    encoding itself in worker processes: lib/bodies.py)."""
    b = ctx.builder
    batch = int(ctx.traffic.get("write_batch", 100))
    rng = np.random.default_rng([ctx.seed, 0x57])
    jobs = []
    for _ in range(count):
        ids = np.sort(rng.choice(b.rows.shape[0], batch, replace=False))
        jobs.append((b.rows.filename, b.rows.shape, b.cls, ids.tolist(),
                     b.dataset.properties(b.cfg, ids)))
    return encode_all(jobs)


def post(url: str, body: bytes, timeout: float):
    """One REST batch -> the reply, or the exception that stands for it."""
    req = urllib.request.Request(url, data=body, method="POST")
    req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=max(timeout, 0.1)) as r:
            res = json.loads(r.read())
        bad = [x for x in res if x["result"]["status"] != "SUCCESS"]
        if bad:
            return RuntimeError(f"batch write: {bad[0]['result']}")
        return res
    except Exception as e:  # noqa: BLE001 — a failed request, counted
        return e


def run(ctx) -> dict:
    t = ctx.traffic
    batch = int(t.get("write_batch", 100))
    period = batch / float(t["write_rows_per_s"])
    timeout = float(t.get("timeout_s", 30.0))
    due = int(math.ceil(ctx.seconds / period))      # batches the window has
    plan = bodies(ctx, min(int(t.get("distinct_writes", 128)), due))
    url = ctx.server.base + "/v1/batch/objects"
    req = Request("write", None, np.empty(0, np.int64))
    records: list = []

    def writer() -> None:
        ctx.started.wait()
        t_end = ctx.t_start + ctx.seconds
        for n in range(due):
            t_due = ctx.t_start + n * period
            delay = t_due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t_sent = time.monotonic()
            if t_sent >= t_end:
                return
            reply = post(url, plan[n % len(plan)], timeout)
            records.append((t_due, t_sent, time.monotonic(), req, reply))

    th = threading.Thread(target=writer, daemon=True)
    th.start()
    window = _closed().run(ctx)
    th.join(timeout + period)
    t_close = window["t_start"] + window["seconds"]
    late = [s - d for d, s, _, _, _ in records]
    window["sender"] = {
        "writer": {
            "due": due, "sent": len(records),
            "acknowledged_in_window": sum(
                1 for _, _, done, _, r in records
                if not isinstance(r, Exception) and done <= t_close),
            "late_p95_s": (float(np.percentile(late, 95)) if late else None),
            "period_s": period, "alive_at_return": th.is_alive()}}
    window["records"] = window["records"] + records
    return window
