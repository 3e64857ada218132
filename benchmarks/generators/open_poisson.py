"""Open loop: Poisson arrivals at a fixed rate, sent on schedule whatever the
server does. Parameters (traffic file): `rate_per_s`, `channels` (client
connections, used in turn), `drain_s` (how long to wait for replies after the
last send). Latency counts from the time a request was DUE, so a stall
lengthens the requests behind it; how late each send ran is recorded."""

from __future__ import annotations

import threading
import time

import numpy as np

SPIN_S = 0.0005   # sleep to within this of the due time, then spin


def schedule(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of every arrival."""
    rng = np.random.default_rng([seed, 0xA1])
    n = int(rate_per_s * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, n))
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate_per_s, n))])
    return due[due < seconds]


def run(ctx) -> dict:
    t = ctx.traffic
    rate = float(ctx.rate_override or t["rate_per_s"])
    due = schedule(ctx.seed, rate, ctx.seconds)
    rng = np.random.default_rng([ctx.seed, 0xA2])
    reqs = [ctx.builder.draw(rng) for _ in range(len(due))]
    conns = [ctx.caller() for _ in range(int(t.get("channels", 1)))]
    records = [None] * len(due)
    pending = [len(due)]
    lock = threading.Lock()
    all_done = threading.Event()

    def make_done(i, t_due, t_sent, req):
        def done(reply, t_done):
            records[i] = (t_due, t_sent, t_done, req, reply)
            with lock:
                pending[0] -= 1
                if pending[0] == 0:
                    all_done.set()
        return done

    # every connection has carried a request before the window: the first
    # call on a channel sets up what the later ones reuse
    warm = np.random.default_rng([ctx.seed, 0xA3])
    warm_failures = 0
    for c in conns:
        try:
            c.call(ctx.builder.draw(warm))
        except Exception:  # noqa: BLE001 — counted; the window's own will tell
            warm_failures += 1
    t_start = time.monotonic() + 0.05
    ctx.window_started(t_start)
    sent_at = [0.0] * len(due)
    # where a late send lost its time: asleep past the wake-up it asked
    # for, or inside the submit before it
    overslept = in_submit = 0.0
    for i, d in enumerate(due):
        t_due = t_start + float(d)
        while True:
            left = t_due - time.monotonic()
            if left <= 0:
                break
            if left > SPIN_S:
                time.sleep(left - SPIN_S)
                overslept = max(overslept, time.monotonic() - t_due)
        t_sent = time.monotonic()
        sent_at[i] = t_sent
        conns[i % len(conns)].submit(reqs[i],
                                     make_done(i, t_due, t_sent, reqs[i]))
        in_submit = max(in_submit, time.monotonic() - t_sent)
    if len(due):
        all_done.wait(timeout=float(t.get("drain_s", 10.0)))
    t_end = time.monotonic()
    out = []
    unfinished = 0
    for i, rec in enumerate(records):
        if rec is None:
            unfinished += 1
            rec = (t_start + float(due[i]), sent_at[i], t_end, reqs[i],
                   TimeoutError("not completed when the drain ended"))
        out.append(rec)
    for c in conns:
        c.close()
    return {"loop": "open", "t_start": t_start, "t_end": t_end,
            "seconds": ctx.seconds, "records": out, "unfinished": unfinished,
            "warm_failures": warm_failures,
            "sender": {"overslept_max_ms": overslept * 1e3,
                       "submit_max_ms": in_submit * 1e3},
            "offered_per_s": len(due) / ctx.seconds}
