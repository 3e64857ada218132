"""One run of one cell of the benchmark, on the served path.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration is recovered from its state directory
(`data/bench/<config>/`, built once per checkout by `benchmarks.build`) by
`python -m weaviate_tpu` running as the one child that owns the chip, with the
program's defaults; this process is a client and never initialises a JAX
backend. Set-up (recovery, backend start, cache load, warm-up of the cell's own
shapes) is timed as `setup_s`; then the cell's traffic runs for `--seconds`;
then the server is stopped with SIGTERM and every reply of the window is held
to the configuration's plain reference, under the filter its query carried.
The last line of stdout is the result object, whose last key `compared` has
every number `correct` was decided from beside its limit (the same lines end
standard error); everything else the run saw is on the `observations:` line
before it and in `chiprun_out/bench/`. No accelerator, or fewer chips than
the cell asks for: exit code 1 and no result line.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()   # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

from benchmarks import build as builder  # noqa: E402
from benchmarks.lib import check, costs, stats, xplane  # noqa: E402
from benchmarks.lib import server as srv  # noqa: E402
from benchmarks.lib.requests import Caller, RequestBuilder, parse_reply  # noqa: E402
from benchmarks.lib.spec import ROOT, Spec  # noqa: E402
from benchmarks.readers import host_gaps  # noqa: E402

STATE_ROOT = os.path.join(ROOT, "data", "bench")     # /data/ is git-ignored
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")
BUILD_LIMIT_S = 1100.0
READY_LIMIT_S = 300.0
STOP_LIMIT_S = 150.0
WARM_LIMIT_S = 240.0
WARM_MAX_REQUESTS = 40
WARM_WINDOW_S = 1.0
TRACE_SECONDS = 5.0


class NoResult(Exception):
    """The run cannot stand for the cell: no result line, exit code 1."""


def log(msg: str) -> None:
    print(msg, flush=True)


class Ctx:
    """What a generator gets: the cell's parameters, a request builder and
    connections; nothing of the harness's bookkeeping."""

    def __init__(self, server, traffic, builder_, seed, seconds,
                 rate_override=None):
        self.server, self.traffic, self.builder = server, traffic, builder_
        self.seed, self.seconds = seed, seconds
        self.rate_override = rate_override
        self.t_start = None
        self.started = threading.Event()

    def caller(self) -> Caller:
        return Caller(self.server, float(self.traffic.get("timeout_s", 30.0)))

    def window_started(self, t_start: float) -> None:
        self.t_start = t_start
        self.started.set()


# -- set-up -------------------------------------------------------------------


def ensure_state(spec: Spec, cfg: dict, state: str,
                 expect_platform: str) -> tuple[dict, float]:
    """The configuration's state directory, built if it is absent or made
    for another configuration. -> (manifest, seconds the build took)."""
    m = builder.read_manifest(state)
    if builder.state_matches(state, cfg) and \
            m["device"]["platform"] == expect_platform:
        return m, 0.0
    log(f"[state] {state}: {'stale' if m else 'absent'}, building")
    t0 = time.monotonic()
    # its own process group: the build has a child of its own, and a build
    # that passes its limit must leave nothing behind
    proc = subprocess.Popen(
        [sys.executable, "-m", "benchmarks.build", "--config", cfg["name"],
         "--state", state, "--expect-platform", expect_platform]
        + spec.as_args(),
        cwd=ROOT, env=srv.child_env(cfg.get("env")), start_new_session=True)
    try:
        rc = proc.wait(timeout=BUILD_LIMIT_S)
    except BaseException:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise
    if rc != 0:
        raise NoResult(f"build child exited {rc}")
    m = builder.read_manifest(state)
    if not builder.state_matches(state, cfg):
        raise NoResult(f"build left no matching manifest in {state}")
    return m, time.monotonic() - t0


def ensure_plan(spec: Spec, cfg: dict, traffic: dict, state: str,
                filters: list):
    """The ground truth of the traffic's filter plan, computed once a state
    directory by the build's numpy child (no chip, no JAX) and kept there.
    -> (gt_ids, rows each query is allowed or None)."""
    truth = builder.load_truth(state, filters)
    if truth is None:
        log(f"[state] {state}: no ground truth of {traffic['name']}'s "
            "filter plan yet, computing")
        t0 = time.monotonic()
        rc = subprocess.call(
            [sys.executable, "-m", "benchmarks.build", "--config",
             cfg["name"], "--state", state, "--ground-truth", "--traffic",
             traffic["name"]] + spec.as_args(),
            cwd=ROOT, env=srv.child_env(), timeout=BUILD_LIMIT_S)
        truth = builder.load_truth(state, filters)
        if rc != 0 or truth is None:
            raise NoResult(f"ground-truth child exited {rc}")
        log(f"[state] filter plan's ground truth in "
            f"{time.monotonic() - t0:.0f}s")
    return truth


def check_identity(meta: dict, chips: int, expect_platform: str) -> dict:
    dev = meta.get("device") or {}
    if dev.get("platform") != expect_platform:
        raise NoResult(f"server runs on platform {dev.get('platform')!r} "
                       f"({dev}), want {expect_platform!r}: no accelerator, "
                       "no result")
    if dev.get("count", 0) < chips:
        raise NoResult(f"server sees {dev.get('count')} devices, the cell "
                       f"asks for {chips}")
    if expect_platform == "tpu":
        costs.peaks(dev["device_kind"])     # not in the table: an error
    return dev


def index_health(server, cls: str) -> dict:
    shards = server.get("/debug/index")["indexes"][cls]
    if len(shards) != 1:
        raise NoResult(f"{cls}: {len(shards)} shards, want 1")
    return next(iter(shards.values()))["vector_index"]


def warm_up(ctx: Ctx, spec: Spec, cache_dir: str) -> dict:
    """The cell's own request kind and width, one at a time, until two
    requests in a row take under twice the one before them and the compile
    cache stops growing; then a short window of the cell's own generator,
    thrown away."""
    conn = Caller(ctx.server, WARM_LIMIT_S)
    rng = np.random.default_rng([ctx.seed, 0xAA])
    times, files = [], []
    t_end = time.monotonic() + WARM_LIMIT_S
    try:
        while len(times) < WARM_MAX_REQUESTS and time.monotonic() < t_end:
            req = ctx.builder.draw(rng)
            t0 = time.monotonic()
            conn.call(req)
            times.append(time.monotonic() - t0)
            files.append(srv.count_files(cache_dir))
            if len(times) >= 3 and max(times[-2:]) < 2 * times[-3] \
                    and files[-1] == files[-3]:
                break
    finally:
        conn.close()
    gen = spec.generator(ctx.traffic["generator"])
    warm = Ctx(ctx.server, ctx.traffic, ctx.builder, ctx.seed + 1_000_003,
               WARM_WINDOW_S, ctx.rate_override)
    gen.run(warm)
    return {"requests": len(times), "first_s": times[0], "last_s": times[-1],
            "cache_files": srv.count_files(cache_dir)}


# -- the window ---------------------------------------------------------------


def quiet_gc(fn, *args):
    """Run `fn` with this process's garbage collector off. The client holds
    a million floats of queries and every reply of the window; a full
    collection over them stalls the generator for 100 ms at a time (seen on
    the chip as three clusters of late sends in 20 s), and the stall would
    be read as the server's tail."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return fn(*args)
    finally:
        gc.enable()
        gc.unfreeze()


def capture_trace(server, ctx: Ctx, seconds: float, out: dict) -> None:
    """Runs in a thread: a quarter into the window, ask the server (the only
    process that can trace the chip) for a device trace."""
    ctx.started.wait()
    delay = ctx.t_start + ctx.seconds / 4.0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    span = max(min(TRACE_SECONDS, ctx.seconds / 2.0), 0.5)
    try:
        out["text"] = server.get(f"/debug/pprof/trace?seconds={span:g}",
                                 timeout=span + 120.0)
        out["seconds"] = span
    except Exception as e:  # noqa: BLE001 — reported, the run goes on
        out["error"] = f"{type(e).__name__}: {e}"


def reduce_window(window: dict, k: int) -> dict:
    """Requests of the window -> arrays. A request that errored, passed its
    deadline or had not completed when the drain ended is failed."""
    recs = window["records"]
    lat, late, fails, first_error = [], [], 0, None
    qidx, ids, dists = [], [], []
    done_in_window = 0
    t_close = window["t_start"] + window["seconds"]
    for t_due, t_sent, t_done, req, reply in recs:
        late.append(t_sent - t_due)
        err = None
        if isinstance(reply, Exception):
            err = f"{type(reply).__name__}: {reply}"
        else:
            i, d, err = parse_reply(req, reply, k)
        if err is not None:
            fails += 1
            first_error = first_error or err[:300]
            continue
        lat.append(t_done - t_due)
        if t_done <= t_close:
            done_in_window += req.queries
        if req.queries:
            qidx.append(req.qidx)
            ids.append(i)
            dists.append(d)
    cat = (lambda xs, shape, dt: np.concatenate(xs) if xs
           else np.empty(shape, dt))
    return {"attempted": len(recs), "failed": fails,
            "first_error": first_error, "latency_s": lat, "late_s": late,
            "queries_done_in_window": done_in_window,
            "qidx": cat(qidx, (0,), np.int64),
            "ids": cat(ids, (0, k), np.int64),
            "dists": cat(dists, (0, k), np.float32)}


def comparisons(answers: dict, w: dict, k: int, prom, health: dict,
                clean: bool, live: int, acknowledged: int, rows: int,
                tail_samples: tuple[int, int] | None) -> list[tuple]:
    """Every number `correct` is decided from, beside its limit:
    (name, value, limit as text, whether it holds, the reason if not)."""
    falls = {json.dumps(lab, sort_keys=True): v for name, lab, v in prom
             if name == "weaviate_device_fallback_total" and v > 0}
    breaker = [v for name, _, v in prom if name == "weaviate_breaker_state"]
    rejected = {name: kern["rejected_shapes"]
                for name, kern in (health.get("kernels") or {}).items()
                if kern.get("rejected") or kern.get("broken")}
    rows_ok = live == acknowledged == rows
    out = [
        ("live_rows", live, f"== {rows}", rows_ok,
         f"not durable: live {live}, acknowledged {acknowledged}, "
         f"rows {rows}"),
        ("recall", answers["recall"], f">= {check.RECALL_BAR}",
         answers["recall"] >= check.RECALL_BAR,
         f"recall {answers['recall']:.4f} < {check.RECALL_BAR}"),
        ("bad_distances", answers["bad_distances"], "== 0",
         answers["bad_distances"] == 0,
         f"{answers['bad_distances']} distances off the reference: "
         f"{answers['first_bad']}"),
        ("unknown_rows", answers["unknown_rows"], "== 0",
         answers["unknown_rows"] == 0,
         f"{answers['unknown_rows']} results name rows that do not exist"),
        ("short_replies", answers["short_replies"], "== 0",
         answers["short_replies"] == 0,
         f"{answers['short_replies']} replies with fewer than "
         f"min({k}, rows the filter allows)"),
        ("disallowed_rows", answers["disallowed_rows"], "== 0",
         answers["disallowed_rows"] == 0,
         f"{answers['disallowed_rows']} returned rows are outside their "
         f"query's filter: {answers['first_disallowed']}"),
        ("fallback_answers", sum(falls.values()), "== 0", not falls,
         f"fallback plane answered: {falls}"),
        ("breaker_state", max(breaker, default=-1.0), "== 0",
         breaker == [0.0], f"breaker state {breaker}"),
        ("rejected_kernel_shapes", len(rejected), "== 0", not rejected,
         f"rejected kernel shapes: {rejected}"),
        ("clean_shutdown", int(clean), "== 1", clean,
         "server did not exit 0 with 'shutdown complete'"),
        ("failed_requests", w["failed"], "== 0", w["failed"] == 0,
         f"{w['failed']} failed requests, first: {w['first_error']}"),
        ("completed_requests", len(w["latency_s"]), ">= 1",
         bool(w["latency_s"]), "no request completed"),
    ]
    if tail_samples is not None:
        have, need = tail_samples
        out.append(("tail_samples", have, f">= {need}", have >= need,
                    f"tail: {have} samples, the percentile needs {need}"))
    return out


# -- one run ------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool,
        expect_platform: str = "tpu", spec: Spec | None = None,
        state_root: str | None = None, sweep: list[float] | None = None,
        keep_trace: str | None = None, t0: float | None = None):
    """-> the result object (None for a sweep). Raises NoResult where the
    run cannot stand for the cell."""
    spec = spec or Spec()
    cell = spec.workload(workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    reference = spec.reference(cfg["reference"])
    dataset = spec.dataset(cfg)
    generator = spec.generator(traffic["generator"])
    chips, k = int(cell["chips"]), int(cfg["k"])
    cls = cfg["class"]["class"]
    try:  # the system under test has to be there
        from weaviate_tpu.grpcapi import weaviate_pb2  # noqa: F401
    except ImportError as e:
        raise NoResult(f"the program is not in this checkout: {e}") from None

    state = os.path.join(state_root or STATE_ROOT, cfg["name"])
    manifest, build_s = ensure_state(spec, cfg, state, expect_platform)
    log(f"[state] {state}: {manifest['disk_bytes'] / 1e9:.2f} GB on disk, "
        f"{manifest['acknowledged']} rows acknowledged"
        + (f", built in {build_s:.0f}s" if build_s else ", found"))
    filters = builder.plan_filters(cfg, traffic, dataset)
    gt_ids, gt_allowed = ensure_plan(spec, cfg, traffic, state, filters)

    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"{workload}-seed{seed}-trace{int(trace)}"
    env = dict(cfg.get("env") or {})
    if trace:
        env["TRACING_ENABLED"] = "true"
    t_server = time.monotonic()
    server = srv.Server(os.path.join(state, "data"),
                        os.path.join(OUT_DIR, tag + ".server.log"), env)
    obs: dict = {"workload": workload, "seed": seed, "seconds": seconds,
                 "trace": bool(trace), "build_s": build_s,
                 "state_disk_bytes": manifest["disk_bytes"]}
    try:
        server.wait_ready(time.monotonic() + READY_LIMIT_S)
        t_ready = time.monotonic()
        obs["ready_s"] = t_ready - t_server
        meta = server.get("/v1/meta")
        dev = check_identity(meta, chips, expect_platform)
        cache_dir = meta.get("compile_cache_dir")
        log(f"[server] ready in {obs['ready_s']:.1f}s on {dev}, compile "
            f"cache {cache_dir} ({srv.count_files(cache_dir)} files)")

        # durability: what the build acknowledged is there after a restart
        health = index_health(server, cls)
        obs["live"], obs["capacity"] = health["live"], health.get("capacity")

        pool = np.load(os.path.join(state, "pool.npy"))
        rows = builder.open_rows(state, int(cfg["rows"]), int(cfg["dim"]))
        req_builder = RequestBuilder(cfg, traffic, pool, filters, rows,
                                     dataset)
        ctx = Ctx(server, traffic, req_builder, seed, float(seconds))

        obs["warm_up"] = warm_up(ctx, spec, cache_dir)
        setup_s = time.monotonic() - (_T0 if t0 is None else t0)
        log(f"[setup] {setup_s:.1f}s (warm-up {obs['warm_up']})")

        if sweep:
            run_sweep(server, spec, traffic, req_builder, generator, seed,
                      sweep, workload, k)
            rc = server.stop(STOP_LIMIT_S)
            log(f"[server] exit {rc}")
            return None

        files_before = srv.count_files(cache_dir)
        traced: dict = {}
        tracer = None
        if trace:
            tracer = threading.Thread(
                target=capture_trace, args=(server, ctx, seconds, traced),
                daemon=True)
            tracer.start()
        t_unix0 = time.time() * 1e3
        window = quiet_gc(generator.run, ctx)
        if tracer is not None:
            tracer.join(timeout=TRACE_SECONDS + 150.0)
        files_after = srv.count_files(cache_dir)
        log(f"[window] {len(window['records'])} requests in "
            f"{window['t_end'] - window['t_start']:.1f}s")
        # the server's LSM cycle first ticks 30 s after its shards opened
        # (PERSISTENCE_LSM_COMPACTION_INTERVAL) and then compacts under the
        # GIL for minutes: the window is meant to end before that tick
        obs["window_after_ready_s"] = [
            window["t_start"] - t_ready,
            window["t_start"] + window["seconds"] - t_ready]

        # what the server counted, before it goes
        sources: dict = {
            "prom": srv.prom_samples(server.metrics_text()),
            "debug_index": server.get("/debug/index"),
            "debug_memory": server.get("/debug/memory"),
            "meta": meta,
            "cell": {"device_kind": dev["device_kind"], "chips": chips,
                     "rows": int(cfg["rows"]), "dim": int(cfg["dim"]),
                     "batch": req_builder.width,
                     "pq_segments": ((cfg["class"].get("vectorIndexConfig")
                                      or {}).get("pq") or {}).get("segments")},
        }
        if trace:
            sources["perf"] = server.get("/debug/perf")
            sources["traces"] = server.get("/debug/traces")
        health = index_health(server, cls)
        sources["index"] = health
        t_stop = time.monotonic()
        rc = server.stop(STOP_LIMIT_S)
        obs["stop_s"] = time.monotonic() - t_stop
        clean = rc == 0 and "shutdown complete" in server.log_text()
        log(f"[server] exit {rc} in {obs['stop_s']:.1f}s")
    except srv.ServerFailed as e:
        raise NoResult(str(e)) from None
    finally:
        server.kill()

    # -- the replies against the reference ------------------------------------
    w = reduce_window(window, k)
    filtered = any(f is not None for f in filters)
    answers = check.check_window(
        reference, cfg["distance"], k, rows, pool, gt_ids, w["qidx"],
        w["ids"], w["dists"],
        allowed_pairs=(lambda qq, rr: builder.allowed_pairs(
            dataset, cfg, filters, qq, rr)) if filtered else None)
    lat_ms = [x * 1e3 for x in w["latency_s"]]
    late_ms = [x * 1e3 for x in w["late_s"]]
    tail_q = float(traffic.get("tail_percentile", 99))
    tail_ms = stats.percentile(lat_ms, tail_q, strict=False) \
        if lat_ms else None
    compared = comparisons(
        answers, w, k, sources["prom"], health, clean=clean,
        live=obs["live"], acknowledged=manifest["acknowledged"],
        rows=int(cfg["rows"]),
        tail_samples=(len(lat_ms), stats.min_samples(tail_q))
        if window["loop"] == "open" else None)
    reasons = [why for _, _, _, ok, why in compared if not ok]
    if filtered:
        obs["filter_plan"] = {
            "filtered_queries": sum(f is not None for f in filters),
            "allowed_rows_min": int(gt_allowed.min()),
            "allowed_rows_median": float(np.median(gt_allowed)),
            "allowed_rows_max": int(gt_allowed.max())}

    values = {
        "setup_s": setup_s,
        "qps": w["queries_done_in_window"] / float(seconds),
        "p50_ms": stats.median(lat_ms) if lat_ms else None,
        "recall": answers["recall"],
    }
    p50 = values["p50_ms"]

    def e2e_value(m: dict):
        if m["name"] in values:
            return values[m["name"]]
        return stats.named_percentile(m["name"], lat_ms)

    client = {
        "send_late_ms": (stats.percentile(late_ms, 99, strict=False)
                             if late_ms else None),
        "offered_per_s": window["offered_per_s"],
        "sender": window.get("sender"),
        "tail_ms": tail_ms,
        # requests that took over three times the window's median: a stall
        # or a background cycle of the server's inside the window
        "over_3x_median_pct": (
            100.0 * sum(x > 3.0 * p50 for x in lat_ms) / len(lat_ms)
            if lat_ms else None),
        "cache_files_added": files_after - files_before,
        "window_start_unix_ms": t_unix0,
        "requests": len(lat_ms),
    }
    obs.update({"answers": answers, "client": client, "reasons": reasons,
                "e2e": values, "unfinished": window["unfinished"],
                "memory_ledger_device": sources["debug_memory"].get("device"),
                "kernels": health.get("kernels")})

    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": int(dev["count"]),
              "memory_peak_bytes": memory_peak_bytes(sources, health)}
    result = {"correct": not reasons, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": {}, "device": device}

    def layer_value(m: dict):
        f = spec.layer_metric(m["name"])
        return spec.reader(f["reader"]).read(sources, **f["params"])

    if trace:
        sources["client"] = client
        found = reduce_trace(sources, traced, state, keep_trace, tag)
        device.update(found.get("device", {}))
        if "breakdown" in found:
            result["breakdown"] = found["breakdown"]
        which, value_of = "per_layer", layer_value
    else:
        which, value_of = "end_to_end", e2e_value
    for m in spec.metrics_for(workload, which):
        value = value_of(m)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:   # after the readers: they leave their notes in `sources`
        obs.update({"trace_capture": found["capture"],
                    "xplane": found.get("summary"),
                    "notes": sources.get("notes"),
                    "perf": sources.get("perf")})
    # beside its limit, every number `correct` was decided from: the last
    # key of the result line and the last lines of standard error
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit, _, _ in compared}
    with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
        json.dump({"observations": obs, "result": result,
                   "latency_ms": lat_ms, "late_ms": late_ms}, f)
    log("observations: " + json.dumps(obs, default=str))
    for name, value, limit, ok, _ in compared:
        print(f"compared: {name} {value} (limit {limit})"
              f"{'' if ok else ' FAILS'}", file=sys.stderr, flush=True)
    return result


def memory_peak_bytes(sources: dict, health: dict) -> int:
    """Device bytes on the fullest chip. The program reports the
    allocator's bytes in use (device 0; per device for a mesh index), not
    its peak: taken after the window, while the slab and the last
    dispatch's buffers are live. Where the backend reports nothing, the
    program's analytic ledger."""
    per_dev = [d.get("allocator_bytes_in_use")
               for d in health.get("per_device") or ()]
    alloc = ((sources["debug_memory"].get("device") or {})
             .get("allocator") or {}).get("allocator_bytes_in_use")
    seen = [int(x) for x in per_dev + [alloc] if x]
    if seen:
        return max(seen)
    return int((sources["debug_memory"].get("device") or {})
               .get("per_device_bytes") or 0)


def reduce_trace(sources: dict, traced: dict, state: str,
                 keep_trace: str | None, tag: str) -> dict:
    """The xplane the server wrote -> sources["xplane"]. -> {"capture": what
    became of the capture, "device": busy_s and window_s for the result's
    `device`, "breakdown": ..., "summary": per-device busy and idle}; only
    "capture" where no device plane was traced."""
    trace_root = os.path.join(state, "data", "traces")
    path = xplane.find_xplane(trace_root)
    capture = {k: v for k, v in traced.items() if k != "text"}
    if path is None:
        capture.setdefault("error", f"no xplane under {trace_root}")
        return {"capture": capture}
    capture["xplane_bytes"] = os.path.getsize(path)
    trace = xplane.load(path)
    if keep_trace:
        import gzip

        os.makedirs(keep_trace, exist_ok=True)
        with gzip.open(os.path.join(keep_trace, tag + ".device_events.json.gz"),
                       "wt") as f:
            f.write(xplane.to_json(trace))
        if os.path.getsize(path) < 16 << 20:
            shutil.copy(path, os.path.join(keep_trace, tag + ".xplane.pb"))
    shutil.rmtree(trace_root, ignore_errors=True)   # traces are large
    if not trace:
        capture["error"] = "the trace holds no device plane"
        return {"capture": capture}
    sources["xplane"] = trace
    summary = xplane.device_summary(trace)
    devs = summary["devices"]
    ops: dict[str, float] = {}
    for d in devs.values():
        for name, s in d["op_seconds"].items():
            ops[name] = ops.get(name, 0.0) + s / len(devs)
    median, _ = xplane.median_device(
        {p: d["idle_pct"] for p, d in devs.items()})
    return {
        "capture": capture,
        "device": {
            "busy_s": sum(d["busy_s"] for d in devs.values()) / len(devs),
            "window_s": summary["window_s"]},
        "breakdown": {
            # by the names the trace gives them (whole HLO instructions,
            # cut); ops nest: a while's seconds include its body's
            "device_ops": [[n[:160], s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": name_idle_gaps(
                trace, (sources.get("perf") or {}).get("capture"),
                summary["window_s"] - devs[median]["busy_s"],
                devs[median]["gaps_s"])},
        "summary": {"window_s": summary["window_s"], "devices": {
            p: {"busy_s": d["busy_s"], "idle_pct": d["idle_pct"],
                "op_events": d["op_events"]} for p, d in devs.items()}},
    }


def name_idle_gaps(trace: dict, capture: dict | None, idle_s: float,
                   longest: list) -> list:
    """`breakdown.idle_gaps`: the median device's idle seconds by the host
    interval of the program that was open in them (readers/host_gaps.py:
    the innermost a thread, split over threads), largest first, then its
    longest single gaps, each named by the interval that held most of it.
    Without the program's capture log nothing is known of the host."""
    if not capture or not capture.get("intervals"):
        return [["unattributed (no capture log): all gaps together",
                 idle_s]] + [
            [f"unattributed: gap at +{at:.3f}s", g] for g, at in longest[:5]]
    label = (lambda name: "no interval open" if name is None else str(name))
    w0, _, gaps = host_gaps.idle_gaps(trace)
    given = host_gaps.attribute(gaps, capture["intervals"])
    out = [[label(name), ns / 1e9] for name, ns in sorted(
        given.items(), key=lambda kv: -kv[1])[:5]]
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:5]:
        inside = host_gaps.attribute([(g0, g1)], capture["intervals"])
        most = max(inside, key=inside.get)
        out.append([f"gap at +{(g0 - w0) / 1e9:.3f}s, mostly {label(most)}",
                    (g1 - g0) / 1e9])
    return out


def run_sweep(server, spec, traffic, req_builder, generator, seed, rates,
              workload, k) -> None:
    """One server lifetime, steps of 10 s at rising rates; written to a
    file and printed, never on a result line."""
    rows = []
    for i, rate in enumerate(rates):
        ctx = Ctx(server, traffic, req_builder, seed + i, 10.0,
                  rate_override=rate)
        w = reduce_window(quiet_gc(generator.run, ctx), k)
        lat = [x * 1e3 for x in w["latency_s"]]
        half = len(lat) // 2
        row = {"rate_per_s": rate, "attempted": w["attempted"],
               "failed": w["failed"],
               "p50_ms": stats.median(lat) if lat else None,
               "p99": stats.percentile(lat, 99, strict=False) if lat
               else None,
               "max_ms": max(lat) if lat else None,
               # a growing backlog shows as a second half slower than the first
               "p50_first_half_ms": stats.median(lat[:half]) if half else None,
               "p50_second_half_ms": stats.median(lat[half:]) if half else None,
               "send_late": stats.percentile(
                   [x * 1e3 for x in w["late_s"]], 99, strict=False)}
        rows.append(row)
        log("sweep: " + json.dumps(row))
        if w["failed"] or not half:
            break       # past the knee: the backlog would spoil the next step
    with open(os.path.join(OUT_DIR, f"sweep-{workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: an open-loop sweep in one "
                         "server lifetime, no result line")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the xplane and its device "
                         "events into: to read a trace by hand, or to cut "
                         "a fixture for the tests (PERF.md section 3)")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json than the checkout's: a "
                         "throw-away set of cells that is no part of the "
                         "benchmark (with --extra-root)")
    ap.add_argument("--extra-root", default=None,
                    help="a directory whose configs/, traffic/, datasets/, "
                         "... are looked in before benchmarks/")
    args = ap.parse_args(argv)
    try:
        spec = Spec(args.benchmark_json, args.extra_root)
        result = run(
            args.workload, args.seed,
            args.seconds if args.seconds is not None else spec.run_seconds,
            bool(args.trace), spec=spec,
            sweep=[float(x) for x in args.sweep.split(",")] if args.sweep
            else None, keep_trace=args.keep_trace)
    except NoResult as e:
        print(f"NO RESULT: {e}", file=sys.stderr, flush=True)
        return 1
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
