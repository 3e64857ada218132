"""The restart's timeline (monitoring/perf.py `Timeline`, `tracing.stage`,
`tracing.StageSums`), on the CPU:

  1. a server started by `python -m weaviate_tpu` over a shard that was
     written and shut down cleanly publishes every stage on `/debug/perf`
     `startup` with tracing off, nested and not overlapping, and the flat
     partition adds up; `/debug/index` `restore.seconds` is the
     `vector.restore` stage; `weaviate_startup_durations_ms` has one sample
     a stage; the way down is one JSON line before "shutdown complete";
  2. the same inner stages from an uncompressed restore, a compressed one
     (a persisted `pq.npz`, its replay cut into runs), the one-device mesh
     and the native graph index;
  3. `startup.memory`: one row a `grow`, at most 512, nulls where the
     backend keeps no allocator statistics;
  4. the compile tally counts a compile where it happens;
  5. with the tracer down start-up constructs no `Phase`; with it up the
     stages are `wv/startup.*` intervals of a capture.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
import uuid

import numpy as np
import pytest

from weaviate_tpu.monitoring import memory, perf, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOP = ("process", "backend", "app", "post_startup", "listen", "first_ready")
IN_SHARD = ("shard.open", "lsm.open", "inverted.open", "log.check",
            "vector.restore")
IN_RESTORE = ("log.read", "log.parse", "stage", "grow", "land", "flush",
              "drain")
PARTS = ("boot", "lsm", "log", "land", "drain", "other", "unaccounted")


@pytest.fixture(autouse=True)
def _no_timeline_left_behind():
    perf.timeline_reset()
    yield
    perf.timeline_reset()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read()


def _build(data_path: str, rows: int = 20000, dim: int = 32) -> None:
    """A class of `rows` objects, written and shut down cleanly."""
    from weaviate_tpu.config import load_config
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    app = App(config=load_config({}), data_path=data_path)
    app.schema.add_class({
        "class": "A", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "n", "dataType": ["int"]}]})
    vecs = np.random.default_rng(0).standard_normal(
        (rows, dim)).astype(np.float32)
    app.db.get_index("A").put_batch([
        StorObj(class_name="A", uuid=str(uuid.UUID(int=i + 1)),
                properties={"n": i}, vector=vecs[i]) for i in range(rows)])
    app.shutdown()


@pytest.fixture(scope="module")
def restarted(tmp_path_factory):
    """One real restart, tracing off: the pages, the metrics and the log
    of a `python -m weaviate_tpu` over a built data directory."""
    data = str(tmp_path_factory.mktemp("restart"))
    _build(data)
    port, grpc_port, metrics_port = _free_port(), _free_port(), _free_port()
    env = dict(os.environ, PROMETHEUS_MONITORING_ENABLED="true",
               PROMETHEUS_MONITORING_PORT=str(metrics_port),
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("TRACING_ENABLED", None)
    t_spawn = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "-m", "weaviate_tpu", "--host", "127.0.0.1",
         "--port", str(port), "--grpc-port", str(grpc_port),
         "--data-path", data],
        env=env, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 120
        # probe until the page shows `first_ready`: REST answers from the
        # moment it listens, the timeline takes the first probe answered
        # after every listener is up (`Timeline.first_ready`), and the stage
        # lands after that probe's reply. Under load the gRPC server is
        # still starting when an early probe succeeds: the wait is on the
        # stage, not on a probe's reply
        while True:
            try:
                _get(base + "/v1/.well-known/ready")
                ready_s = time.monotonic() - t_spawn
                page = json.loads(_get(base + "/debug/perf"))
                if "first_ready" in page["startup"]["stages"]:
                    break
            except OSError:
                pass
            assert proc.poll() is None and time.monotonic() < deadline
            time.sleep(0.1)
        index = json.loads(_get(base + "/debug/index"))
        prom = _get(f"http://127.0.0.1:{metrics_port}/metrics").decode()
    finally:
        proc.send_signal(signal.SIGTERM)
        log = proc.communicate(timeout=120)[0]
    return {"page": page, "index": index, "prom": prom, "log": log,
            "ready_s": ready_s, "rc": proc.returncode}


def test_every_stage_from_process_start_to_first_ready(restarted):
    page = restarted["page"]
    assert page["enabled"] is False      # tracing off: the timeline is kept
    st = page["startup"]
    assert st["anchor"] in ("os", "main")
    want = set(TOP + IN_SHARD + IN_RESTORE)
    if st["anchor"] == "main":
        want.discard("process")
    assert want <= set(st["stages"]), sorted(want - set(st["stages"]))
    for name, s in st["stages"].items():
        assert s["seconds"] >= 0 and s["start_ms"] >= 0, name
    assert st["parallel"] is False and st["dropped"] == 0
    assert st["stages"]["vector.restore"]["stats"]["rows"] == 20000


def _whole(iv):
    """Intervals that ran once, as (name, tid, start_s, end_s)."""
    return [(n, tid, ms / 1e3, ms / 1e3 + s) for n, tid, ms, s, stats in iv
            if "pieces" not in stats]


def test_children_lie_inside_parents_and_nothing_overlaps(restarted):
    iv = restarted["page"]["startup"]["intervals"]
    whole = _whole(iv)
    by = {}
    for n, tid, a, b in whole:
        by.setdefault(n, []).append((a, b))
    eps = 2e-3    # the page rounds to a millisecond and a microsecond

    def inside(child, parent):
        (pa, pb), = by[parent]
        for a, b in by[child]:
            assert pa - eps <= a and b <= pb + eps, (child, parent)

    for child in ("shard.open",):
        inside(child, "app")
    for child in ("lsm.open", "inverted.open", "log.check",
                  "vector.restore"):
        inside(child, "shard.open")
    # a restore's inner stages are sums of pieces: from their first start
    # their summed seconds fit before the restore ends, their span too, and
    # being exclusive they add up to no more than the restore
    (ra, rb), = by["vector.restore"]
    inner = 0.0
    for n, tid, ms, s, stats in iv:
        if "pieces" not in stats:
            continue
        assert n in IN_RESTORE and stats["pieces"] >= 1
        assert ra - eps <= ms / 1e3
        assert ms / 1e3 + max(s, stats["span_s"]) <= rb + eps, n
        inner += s
    assert inner <= (rb - ra) + eps
    # on one thread two intervals are nested or apart, never astride
    main_tid = [tid for n, tid, _, _ in whole if n == "app"][0]
    mine = sorted((a, b, n) for n, tid, a, b in whole if tid == main_tid)
    for i, (a, b, n) in enumerate(mine):
        for a2, b2, n2 in mine[i + 1:]:
            assert a2 >= b - eps or b2 <= b + eps, (n, n2)
    # the stages main() opens follow one another
    order = [n for n in TOP if n in by and n != "first_ready"]
    for prev, nxt in zip(order, order[1:]):
        assert by[prev][0][1] <= by[nxt][0][0] + eps, (prev, nxt)
    assert by["listen"][0][1] <= by["first_ready"][0][1] + eps


def test_the_flat_partition_adds_up_to_ready(restarted):
    sec = restarted["page"]["startup"]["seconds"]
    assert set(sec) == set(PARTS) | {"ready"}
    assert all(v is not None for v in sec.values())
    assert abs(sec["ready"] - sum(sec[p] for p in PARTS)) < 1e-3
    for p in PARTS:
        assert sec[p] >= -1e-6, (p, sec[p])
    stages = restarted["page"]["startup"]["stages"]
    assert abs(sec["log"] - sum(stages[n]["seconds"] for n in
                                ("log.check", "log.read", "log.parse"))) < 1e-3
    assert abs(sec["land"] - sum(stages[n]["seconds"] for n in
                                 ("stage", "grow", "land", "flush"))) < 1e-3
    # what is left are the gaps between main()'s stages
    assert sec["unaccounted"] < 0.05 * sec["ready"] + 0.05
    # under what a client that polls for readiness saw, and close to it. The
    # OS anchor is the process's start in whole clock ticks, rounded down
    # (`perf.process_start_ns`: 10 ms a tick), so the page's `ready` may read
    # up to one tick over the client's own clock when the probe that sealed
    # the timeline came right behind the listeners
    tick = (1.0 / os.sysconf("SC_CLK_TCK")
            if restarted["page"]["startup"]["anchor"] == "os" else 0.0)
    assert sec["ready"] < restarted["ready_s"] + tick
    assert restarted["ready_s"] - sec["ready"] < 2.0


def test_restore_on_debug_index_is_the_vector_restore_stage(restarted):
    (shard,) = restarted["index"]["indexes"]["A"].values()
    restore = shard["vector_index"]["restore"]
    stages = restarted["page"]["startup"]["stages"]
    assert abs(restore["seconds"]
               - stages["vector.restore"]["seconds"]) <= 1e-3
    assert restore["mode"] == "uncompressed" and restore["rows"] == 20000
    assert set(restore["stages"]) == {"log", "land", "drain"}
    assert restore["replay"] == {}
    inner = sum(stages[n]["seconds"] for n in ("log.read", "log.parse"))
    assert abs(restore["stages"]["log"] - inner) < 1e-3


def test_memory_rows_on_a_backend_without_allocator_statistics(restarted):
    st = restarted["page"]["startup"]
    rows = st["memory"]
    assert 0 < len(rows) <= perf.Timeline.MEMORY_ROWS_MAX
    for t_ms, event, capacity, in_use, peak in rows:
        assert t_ms >= 0 and isinstance(event, str)
        assert in_use is None and peak is None       # cpu keeps none
    grows = [r for r in rows if r[1] == "grow"]
    assert len(grows) == st["stages"]["grow"]["stats"]["pieces"] >= 1
    assert grows[-1][2] == st["stages"]["vector.restore"]["stats"]["capacity"]
    assert {"backend", "app", "vector.restore", "listen"} <= {
        r[1] for r in rows}
    assert st["peak_at_restore_end_bytes"] is None
    assert [r[0] for r in rows] == sorted(r[0] for r in rows)


def test_compiles_are_counted_since_the_process_started(restarted):
    page = restarted["page"]
    tally, inside = page["compiles"], page["startup"]["compiles"]
    assert tally["count"] >= inside["count"] > 0
    assert tally["seconds"] >= inside["seconds"] > 0
    assert set(inside) == {"count", "seconds", "cache_hits", "cache_misses"}
    assert 0 < len(tally["last"]) <= perf.CompileTally.KEPT
    t_ms, seconds, hit, stage, name = tally["last"][0]
    assert seconds > 0 and hit in (True, False, None)
    assert stage in IN_RESTORE and name.startswith("jit(")


def test_startup_durations_has_one_sample_a_stage(restarted):
    stages = restarted["page"]["startup"]["stages"]
    counts = {}
    for line in restarted["prom"].splitlines():
        if line.startswith("weaviate_startup_durations_ms_count{"):
            op = line.split('operation="')[1].split('"')[0]
            counts[op] = float(line.rsplit(" ", 1)[1])
    assert set(counts) == set(stages)
    assert set(counts.values()) == {1.0}
    for dead in ("weaviate_startup_progress", "weaviate_lsm_compactions",
                 "weaviate_schema_tx_total", "weaviate_queries_durations_ms"):
        assert dead not in restarted["prom"]


def test_the_way_down_is_one_line_before_shutdown_complete(restarted):
    assert restarted["rc"] == 0
    lines = restarted["log"].splitlines()
    (start,) = [ln for ln in lines if ln.startswith("startup: ")]
    sec = json.loads(start[len("startup: "):])
    assert set(sec) == set(PARTS) | {"ready"}
    (down,) = [ln for ln in lines if ln.startswith("shutdown: ")]
    assert lines.index(down) == lines.index("shutdown complete") - 1
    doc = json.loads(down[len("shutdown: "):])
    assert doc["anchor"] == "signal" and doc["seconds"] > 0
    assert {"grpc.stop", "rest.stop", "app.shutdown", "profiler.stop",
            "db.shutdown", "lsm.close", "vector.close"} <= set(doc["stages"])
    assert doc["stages"]["lsm.close"]["stats"]["sweep_in_flight"] is False
    total = sum(doc["stages"][n]["seconds"]
                for n in ("grpc.stop", "rest.stop", "app.shutdown"))
    assert total <= doc["seconds"] + 1e-3


# -- 2. the same inner stages from every kind of restore ---------------------


def _restore_case(kind: str, path: str):
    """Build an index of `kind` at `path`, close it, and return a function
    that opens it again."""
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config
    from weaviate_tpu.index import new_vector_index

    rng = np.random.default_rng(1)
    if kind == "compressed":
        from weaviate_tpu.index import tpu

        rows, dim = tpu._REPLAY_RUN_MAX + tpu._CHUNK + 100, 16
        cfg = {"distance": "l2-squared",
               "pq": {"enabled": True, "segments": 4, "centroids": 16,
                      "trainingLimit": 512}}
        index_type = "hnsw_tpu"
    elif kind == "mesh":
        rows, dim = 20000, 16
        cfg = {"distance": "cosine", "meshDevices": 1}
        index_type = "hnsw_tpu_mesh"
    elif kind == "graph":
        rows, dim = 600, 16
        cfg = {"distance": "l2-squared"}
        index_type = "hnsw"
    else:
        rows, dim = 20000, 16
        cfg = {"distance": "cosine"}
        index_type = "hnsw_tpu"
    vecs = rng.standard_normal((rows, dim)).astype(np.float32)

    def open_():
        return new_vector_index(
            parse_and_validate_config(index_type, cfg), path, "s0")

    idx = open_()
    idx.add_batch(np.arange(rows), vecs)
    if kind == "graph":
        # a clean shutdown folds the delta into the snapshot: leave both
        idx.flush()
        idx.add_batch(np.arange(rows, rows + 50),
                      rng.standard_normal((50, dim)).astype(np.float32))
        idx._log.flush()
        idx._log.close()
    else:
        idx.shutdown()
    return open_, rows


@pytest.mark.parametrize("kind", ["uncompressed", "compressed", "mesh",
                                  "graph"])
def test_every_kind_of_restore_publishes_the_same_stages(kind, tmp_path):
    open_, rows = _restore_case(kind, str(tmp_path))
    tl = perf.startup_begin()
    idx = open_()
    try:
        doc = tl.summary()
        stages = doc["stages"]
        want = {"log.check", "vector.restore", "log.read", "log.parse",
                "land"}
        if kind != "graph":
            want |= {"stage", "grow", "flush", "drain"}
        assert want <= set(stages), sorted(want - set(stages))
        assert not set(stages) - set(TOP + IN_SHARD + IN_RESTORE)
        restore = idx.last_restore
        assert set(restore) >= {"mode", "rows", "seconds", "stages",
                                "replay"}
        assert set(restore["stages"]) == {"log", "land", "drain"}
        assert abs(restore["seconds"]
                   - stages["vector.restore"]["seconds"]) <= 1e-3
        assert restore["mode"] == {"compressed": "compressed",
                                   "graph": "graph"}.get(kind, "uncompressed")
        if kind == "graph":
            assert restore["rows"] == 50         # the delta's records
        else:
            assert restore["rows"] == rows
            assert len(idx) == rows
        inner = sum(stages[n]["seconds"] for n in stages if n in IN_RESTORE)
        assert inner <= stages["vector.restore"]["seconds"] + 1e-3
        sec = doc["seconds"]
        assert sec["ready"] is None and sec["unaccounted"] is None
        assert abs(sec["land"] - sum(
            stages[n]["seconds"] for n in ("stage", "grow", "land", "flush")
            if n in stages)) < 1e-3
        if kind == "compressed":
            from weaviate_tpu.index import tpu

            # the replay was cut into runs: a parse piece and a land piece
            # a run, a grow a doubling
            runs = -(-rows // tpu._REPLAY_RUN_MAX)
            assert stages["land"]["stats"]["pieces"] >= runs
            assert stages["log.parse"]["stats"]["pieces"] >= runs
            assert idx.compressed and restore["chunks_encoded"] > 0
        health = getattr(idx, "health", None)
        if health is not None:
            assert health()["restore"] == restore
    finally:
        idx.shutdown()


def test_an_index_that_begins_empty_has_no_restore_to_tell(tmp_path):
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config
    from weaviate_tpu.index import new_vector_index

    tl = perf.startup_begin()
    idx = new_vector_index(parse_and_validate_config(
        "hnsw_tpu", {"distance": "l2-squared"}), str(tmp_path), "s0")
    try:
        assert idx.last_restore["rows"] == 0
        stages = tl.summary()["stages"]
        assert "vector.restore" in stages and "log.check" not in stages
        assert "grow" not in stages and "land" not in stages
    finally:
        idx.shutdown()


def test_outside_a_restart_a_restore_keeps_its_record_and_no_timeline(
        tmp_path):
    open_, rows = _restore_case("uncompressed", str(tmp_path))
    assert perf.timeline() is None and perf.startup() is None
    idx = open_()
    try:
        assert idx.last_restore["rows"] == rows
        assert idx.last_restore["seconds"] > 0
        assert idx.last_restore["stages"]["land"] > 0
        assert perf.startup() is None
    finally:
        idx.shutdown()


# -- 3. the device's memory on the same clock --------------------------------


def test_memory_rows_are_capped_and_carry_what_the_allocator_says(
        monkeypatch):
    readings = iter(range(10 ** 6))
    monkeypatch.setattr(memory, "fullest_allocator",
                        lambda: (next(readings), 10 ** 9))
    tl = perf.Timeline(time.perf_counter_ns(), "main")
    assert tl.memory("land", 1 << 20, force=True)[2:] == [1 << 20, 0, 10 ** 9]
    assert tl.memory("land", 1 << 20) is None          # once a second
    for i in range(perf.Timeline.MEMORY_ROWS_MAX + 40):
        tl.memory("grow", 2 << i % 8, force=True)
    doc = tl.summary()
    assert len(doc["memory"]) == perf.Timeline.MEMORY_ROWS_MAX
    assert doc["memory_dropped"] == 41
    tl.note("vector.restore", time.perf_counter_ns(), 5, capacity=64)
    assert tl.peak_at_restore_end == 10 ** 9
    assert tl.summary()["peak_at_restore_end_bytes"] == 10 ** 9


def test_memory_rows_carry_nulls_where_the_backend_keeps_none(monkeypatch):
    monkeypatch.setattr(memory, "allocator_stats", lambda: [{}])
    assert memory.fullest_allocator() is None
    tl = perf.Timeline(time.perf_counter_ns(), "main")
    assert tl.memory("grow", 32768, force=True)[2:] == [32768, None, None]
    monkeypatch.setattr(memory, "allocator_stats", lambda: None)
    assert tl.memory("grow", 65536, force=True)[2:] == [65536, None, None]
    tl.note("vector.restore", time.perf_counter_ns(), 5)
    assert tl.summary()["peak_at_restore_end_bytes"] is None


def test_the_fullest_device_is_the_one_read(monkeypatch):
    monkeypatch.setattr(memory, "allocator_stats", lambda: [
        {"bytes_in_use": 5, "peak_bytes_in_use": 50},
        {"bytes_in_use": 9, "peak_bytes_in_use": 20}, {}])
    assert memory.fullest_allocator() == (9, 50)


def test_the_way_down_takes_no_memory_rows():
    down = perf.shutdown_begin()
    with tracing.stage("rest.stop"):
        pass
    doc = json.loads(down.line())
    assert doc["anchor"] == "signal" and "rest.stop" in doc["stages"]
    assert down.summary()["memory"] == []


# -- the partition, the union, the seal ---------------------------------------


def _ns(seconds: float) -> int:
    return int(seconds * 1e9)


def test_shards_that_open_on_two_threads_publish_the_union():
    tl = perf.Timeline(0, "main")
    rows = [("app", 1, 0.0, 10.0), ("shard.open", 2, 1.0, 6.0),
            ("shard.open", 3, 3.0, 6.0), ("lsm.open", 2, 1.0, 3.0),
            ("lsm.open", 3, 3.0, 3.0), ("listen", 1, 10.0, 1.0)]
    for name, tid, start, length in rows:
        tl._intervals.append((name, tid, _ns(start), _ns(length), {}))
    doc = tl.summary()
    assert doc["parallel"] is True
    # two opens of 6 s over 8 s of wall clock: the parts inside are cut to
    # 8/12 of their sums
    assert doc["seconds"]["lsm"] == pytest.approx(6.0 * 8 / 12)
    assert doc["stages"]["shard.open"]["stats"]["count"] == 2
    sec = doc["seconds"]
    assert sec["ready"] == 11.0
    assert sec["ready"] == pytest.approx(sum(sec[p] for p in PARTS))


def test_shards_opened_one_after_another_are_summed():
    tl = perf.Timeline(0, "main")
    for name, start, length, stats in [
            ("shard.open", 1.0, 2.0, {"shard": "a"}),
            ("shard.open", 3.0, 2.0, {"shard": "b"}),
            ("grow", 1.0, 0.5, {"pieces": 3, "span_s": 1.0}),
            ("grow", 3.0, 0.25, {"pieces": 2, "span_s": 0.5})]:
        tl._intervals.append((name, 7, _ns(start), _ns(length), stats))
    doc = tl.summary()
    assert doc["parallel"] is False
    assert doc["stages"]["shard.open"]["seconds"] == 4.0
    assert doc["stages"]["shard.open"]["stats"] == {"shard": "b", "count": 2}
    assert doc["stages"]["grow"]["stats"]["pieces"] == 5
    assert doc["stages"]["grow"]["start_ms"] == 1000.0


def test_after_ready_the_timeline_takes_first_ready_and_nothing_else():
    from weaviate_tpu.monitoring import noop_metrics

    m = noop_metrics()
    tl = perf.startup_begin()
    with tracing.stage("listen"):
        pass
    sec = tl.ready(m)
    assert sec["ready"] is not None and perf.timeline() is None
    with tracing.stage("shard.open", shard="made-at-run-time"):
        pass
    assert "shard.open" not in tl.summary()["stages"]
    tl.first_ready(m)
    tl.first_ready(m)                 # the second probe is not the first
    doc = tl.summary()
    assert [n for n, *_ in doc["intervals"]].count("first_ready") == 1
    text = m.expose().decode()
    assert 'weaviate_startup_durations_ms_count{operation="listen"} 1.0' \
        in text
    assert 'weaviate_startup_durations_ms_count{operation="first_ready"} 1.0' \
        in text
    assert perf.startup() is tl


def test_stage_sums_are_exclusive():
    sums = tracing.StageSums()
    sums.enter("flush")
    time.sleep(0.01)
    sums.enter("land")
    time.sleep(0.02)
    sums.enter("grow")
    time.sleep(0.01)
    sums.leave()
    sums.leave()
    sums.leave()
    got = list(sums.timed(iter([1, 2, 3]), "log.parse"))
    assert got == [1, 2, 3]
    assert sums.seconds("flush") < sums.seconds("land")
    assert 0.009 < sums.seconds("grow") < sums.seconds("land")
    whole = sums.seconds("flush", "land", "grow")
    assert 0.039 < whole < 0.2
    assert sums.seconds("nothing") == 0.0
    assert tracing.piece_of(None, "land") is tracing.piece_of(None, "grow")


def test_process_start_is_before_now_and_after_boot():
    start = perf.process_start_ns()
    if start is None:
        pytest.skip("the OS gives no start time")
    age_s = (time.perf_counter_ns() - start) / 1e9
    assert 0 < age_s < 24 * 3600
    tl = perf.startup_begin()
    assert tl.anchor == "os"
    assert tl.summary()["stages"]["process"]["seconds"] == pytest.approx(
        age_s, abs=1.0)


# -- 4. the compile tally ------------------------------------------------------


def test_a_forced_compile_raises_the_tally():
    import jax
    import jax.numpy as jnp

    perf.compiles.install()
    perf.compiles.install()           # once a process
    x, y = jnp.ones((7, 3)), jnp.ones((5,))   # their fills compile too
    x.block_until_ready(), y.block_until_ready()
    before = perf.compiles.summary()
    tl = perf.startup_begin()
    salt = float(time.time_ns() % 10 ** 6)    # a program no cache has seen

    @jax.jit
    def never_seen(x):
        return x * salt + 3.0

    with tracing.stage("backend"):
        never_seen(x).block_until_ready()
    after = perf.compiles.summary()
    assert after["count"] == before["count"] + 1
    assert after["seconds"] > before["seconds"]
    t_ms, seconds, hit, stage, name = after["last"][-1]
    assert stage == "backend" and "never_seen" in name and seconds > 0
    assert hit in (False, None)
    assert tl.summary()["compiles"]["count"] == 1
    never_seen(x).block_until_ready()         # no new program
    assert perf.compiles.summary()["count"] == after["count"]
    tl.ready()
    tl.first_ready()

    @jax.jit
    def after_the_seal(x):
        return x - salt

    after_the_seal(y).block_until_ready()
    assert perf.compiles.summary()["count"] == after["count"] + 1
    assert tl.summary()["compiles"]["count"] == 1
    assert perf.compiles.summary()["last"][-1][3] is None


# -- 5. the tracer: down constructs nothing, up annotates ----------------------


def _reopen_app(data_path, tracing_on: bool):
    from weaviate_tpu.config import load_config
    from weaviate_tpu.server import App

    env = {"TRACING_ENABLED": "true"} if tracing_on else {}
    return App(config=load_config(env), data_path=data_path)


def test_with_the_tracer_down_startup_constructs_no_phase(
        tmp_path, monkeypatch):
    data = str(tmp_path)
    _build(data, rows=20000, dim=16)
    made = []
    real = tracing.Phase.__init__

    def spy(self, name, **stats):
        made.append(name)
        real(self, name, **stats)

    monkeypatch.setattr(tracing.Phase, "__init__", spy)
    tl = perf.startup_begin()
    with tracing.stage("app"):
        app = _reopen_app(data, tracing_on=False)
    try:
        with tracing.stage("post_startup"):
            app.db.post_startup()
        assert made == []
        stages = tl.summary()["stages"]
        assert set(IN_SHARD + IN_RESTORE) <= set(stages)
        assert stages["vector.restore"]["stats"]["rows"] == 20000
    finally:
        app.shutdown()


def test_with_the_tracer_up_the_stages_are_intervals_of_a_capture(tmp_path):
    data = str(tmp_path / "d")
    _build(data, rows=20000, dim=16)
    t = tracing.configure(tracing.Tracer())
    w = perf.configure(perf.PerfWindow())
    idx = None
    try:
        from weaviate_tpu.entities.vectorindex import \
            parse_and_validate_config
        from weaviate_tpu.index import new_vector_index

        (shard_dir,) = [os.path.join(data, "a", s)
                        for s in os.listdir(os.path.join(data, "a"))]
        perf.startup_begin()
        w.capture_begin()
        t0 = time.perf_counter_ns()
        idx = new_vector_index(parse_and_validate_config(
            "hnsw_tpu", {"distance": "l2-squared"}), shard_dir, "s0")
        w.capture_end(t0, time.perf_counter_ns(), {})
        names = {iv[0] for iv in w.last_capture()["intervals"]}
        assert {"startup.vector.restore", "startup.log.check",
                "startup.log.read", "startup.stage", "startup.grow",
                "startup.land", "startup.drain"} <= names
        # a piece a generator step is a pair of stamps, never an object
        assert "startup.log.parse" not in names
    finally:
        if idx is not None:
            idx.shutdown()
        perf.unconfigure(w)
        tracing.unconfigure(t)
