"""Closed loop: `callers` clients, each sends its next request when the last
one returns. Parameters (traffic file): `callers`, `distinct_requests` (each
caller cycles through that many requests drawn from the seed before the
window, so nothing is built inside it)."""

from __future__ import annotations

import threading
import time

import numpy as np


def run(ctx) -> dict:
    t = ctx.traffic
    callers = int(t["callers"])
    distinct = int(t.get("distinct_requests", 16))
    # one stream per caller, all from the seed
    streams = np.random.default_rng(ctx.seed).spawn(callers)
    plans = [[ctx.builder.draw(rng) for _ in range(distinct)]
             for rng in streams]
    conns = [ctx.caller() for _ in range(callers)]
    records = [[] for _ in range(callers)]
    barrier = threading.Barrier(callers + 1)
    t_end = [0.0]

    def loop(i: int) -> None:
        conn, plan, out = conns[i], plans[i], records[i]
        barrier.wait()
        n = 0
        while True:
            req = plan[n % len(plan)]
            t0 = time.monotonic()
            if t0 >= t_end[0]:
                return
            try:
                reply = conn.call(req)
            except Exception as e:  # noqa: BLE001 — a failed request, counted
                reply = e
            out.append((t0, t0, time.monotonic(), req, reply))
            n += 1

    threads = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(callers)]
    for th in threads:
        th.start()
    t_start = time.monotonic()
    t_end[0] = t_start + ctx.seconds
    ctx.window_started(t_start)
    barrier.wait()
    for th in threads:
        th.join()
    for c in conns:
        c.close()
    # a request counts in the window it started in; the loop is closed, so
    # every one of them has returned by now
    return {"loop": "closed", "t_start": t_start,
            "t_end": time.monotonic(), "seconds": ctx.seconds,
            "records": [r for rec in records for r in rec],
            "unfinished": 0, "offered_per_s": None}
