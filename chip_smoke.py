"""Chip smoke: the served path, end to end, on the accelerator.

Starts `python -m weaviate_tpu` as ONE child process (it owns the chip; this
process never initialises a JAX backend), and drives it as a client would:
REST `/v1/batch/objects` for writes, gRPC `Search` / `BatchSearch` for reads.

Deployment: BASELINE.json config 1 — 1,000,000 x 128-d float32, l2-squared,
k=10, one `hnsw_tpu` shard — with data from a clustered generator
(no dataset can be fetched; docs/dataset_download_attempts.md). Then two
200,000-row PQ classes so that each remaining Pallas kernel is compiled by
Mosaic once, and, when the server reports several devices, the same 1M rows
again in an `hnsw_tpu_mesh` class.

It is not a benchmark. Every number it prints is an observation of one run,
labelled as such. What it decides is pass or fail:

- the server says it runs on `tpu` (its own `/v1/meta`, not this process's
  environment) — checked before any data is loaded;
- answers agree with exact float32 brute force over the same data and the
  same filter: recall@10 >= 0.95 per query set, returned distances within a
  stated relative tolerance of the true distance of the returned row;
- each Pallas kernel driven has >= 1 validated and 0 rejected compiled
  shapes (`/debug/index` health()["kernels"]) — the positive proof that the
  answers did not come from a fallback tier;
- `weaviate_device_fallback_total` has no sample above zero, the breaker is
  closed, no native library build failed, the server exits 0 on SIGTERM.

Every phase has its own time limit and names itself on failure. Exit code 0
only if every phase passed; then the last two lines of stdout are
`observations: {...}` (everything the run measured) and the result line
`{"ok": true, "device": {"platform", "kind", "count"}}` with exactly those
keys. A failed run prints neither.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

ROWS = 1_000_000        # BASELINE.json config 1
PQ_ROWS = 200_000       # each compressed-tier class
DIM = 128               # never cut
K = 10
BATCH = 256             # BatchSearch width (b >= 8 takes the gmin kernel)
N_SINGLE = 8            # Search requests (b = 1 takes the lax.scan tier)
N_FILTERED = 64         # filtered requests: the server batches no filtered
                        # slots, so each is its own b = 1 masked scan
FILTER_BUCKETS = 10     # `bucket == 3` keeps ~10% of the rows
RECALL_BAR = 0.95       # BASELINE.json
# a returned distance against the float32 distance of the returned row: the
# last stage of every tier checked here rescores elementwise in f32, so
# 1e-3 relative is rounding with room (a distance taken from a bf16 matmul
# pass would miss it by 10x). The funnel's last stage reads bf16 copies of
# the rows: each component is off by at most 2^-9 of itself, which moves
# the distance by at most 2*sqrt(d)*e + e^2 with e = 2^-9 * |row|.
DIST_RTOL = 1e-3
BF16_EPS = 2.0 ** -9
IMPORT_BATCH = 2000
IMPORT_THREADS = 4
OVERALL_LIMIT_S = 1150.0  # the driver's limit is 1200 s


class PhaseFailed(Exception):
    pass


# -- transport ----------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http(method: str, url: str, body=None, timeout: float = 30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=max(timeout, 0.1)) as r:
        raw = r.read()
    if not raw:
        return None
    try:
        return json.loads(raw)
    except ValueError:
        return raw.decode("utf-8", "replace")


class Server:
    """The one child process that owns the chip."""

    def __init__(self, workdir: str):
        self.port, self.grpc_port, self.metrics_port = (
            _free_port(), _free_port(), _free_port())
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, "server.log")
        env = dict(os.environ)
        env.update({
            "PROMETHEUS_MONITORING_ENABLED": "true",
            "PROMETHEUS_MONITORING_PORT": str(self.metrics_port),
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "weaviate_tpu", "--host", "127.0.0.1",
             "--port", str(self.port), "--grpc-port", str(self.grpc_port),
             "--data-path", os.path.join(workdir, "data")],
            env=env, cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT)
        self._channel = self._stubs = None

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def wait_ready(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited rc={self.proc.returncode} before it was "
                    f"ready:\n{self.log_text()[-3000:]}")
            try:
                _http("GET", self.base + "/v1/.well-known/ready", timeout=2)
                return
            except (OSError, urllib.error.URLError):
                time.sleep(0.25)
        raise PhaseFailed("server never answered /v1/.well-known/ready")

    def grpc(self):
        """(Search, BatchSearch) callables. Built here, not taken from
        server/grpc_server.SearchClient: importing the server imports jax,
        and this process stays off it."""
        if self._stubs is None:
            import grpc

            from weaviate_tpu.grpcapi import weaviate_pb2 as pb

            self._channel = grpc.insecure_channel(
                f"127.0.0.1:{self.grpc_port}",
                options=[("grpc.max_receive_message_length", 256 << 20),
                         ("grpc.max_send_message_length", 256 << 20)])
            svc = "/weaviatetpu.v1.Weaviate/"
            self._stubs = (
                self._channel.unary_unary(
                    svc + "Search",
                    request_serializer=pb.SearchRequest.SerializeToString,
                    response_deserializer=pb.SearchReply.FromString),
                self._channel.unary_unary(
                    svc + "BatchSearch",
                    request_serializer=pb.BatchSearchRequest.SerializeToString,
                    response_deserializer=pb.BatchSearchReply.FromString),
            )
        return self._stubs

    def _close_channel(self) -> None:
        if self._channel is not None:
            self._channel.close()
            self._channel = self._stubs = None

    def stop(self, timeout: float) -> int:
        """SIGTERM, then wait; a child that outlives the limit is killed."""
        self._close_channel()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=max(timeout, 1.0))
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                raise PhaseFailed(
                    f"server ignored SIGTERM for {timeout:.0f}s; killed")
        return self.proc.returncode

    def kill(self) -> None:
        self._close_channel()
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()


# -- data and reference -------------------------------------------------------


N_CLUSTERS = 1024


def make_data(n: int, dim: int, rng) -> np.ndarray:
    """SIFT-like clustered distribution: a mixture of gaussians."""
    centers = rng.standard_normal((N_CLUSTERS, dim), dtype=np.float32) * 2.0
    assign = rng.integers(0, N_CLUSTERS, n)
    return centers[assign] + 0.35 * rng.standard_normal((n, dim), dtype=np.float32)


def make_dataset(seed: int, rows: int):
    """(vectors [rows, DIM] f32, single queries, batch queries), all from
    `seed`: the clustered generator, queries = stored rows plus noise."""
    rng = np.random.default_rng(seed)
    vecs = make_data(rows, DIM, rng)
    picks = rng.integers(0, rows, N_SINGLE + BATCH)
    queries = vecs[picks] + 0.05 * rng.standard_normal(
        (picks.size, DIM), dtype=np.float32)
    return vecs, queries[:N_SINGLE], queries[N_SINGLE:]


def exact_topk(vecs: np.ndarray, queries: np.ndarray, k: int,
               allow: np.ndarray | None = None) -> np.ndarray:
    """Exact float32 brute force (a chunked BLAS matmul in numpy, L2)
    -> [Q, k] row ids, restricted to the rows in `allow` when given."""
    rows = vecs if allow is None else vecs[allow]
    norms = (rows.astype(np.float32) ** 2).sum(1)
    out = []
    for s in range(0, len(queries), 256):
        q = queries[s:s + 256].astype(np.float32)
        d = (q ** 2).sum(1, keepdims=True) - 2.0 * (q @ rows.T) + norms[None, :]
        part = np.argpartition(d, k, axis=1)[:, :k]
        for i in range(q.shape[0]):
            out.append(part[i][np.argsort(d[i, part[i]], kind="stable")])
    ids = np.stack(out)
    return ids if allow is None else allow[ids]


def check_answers(name: str, vecs, queries, got_ids, got_dists, want_ids,
                  row_eps: float = 0.0) -> float:
    """recall@K against `want_ids`, and every returned distance against the
    float32 distance of the row that was returned (`row_eps`: relative
    rounding of the stored rows the last stage reads). -> measured recall."""
    hits = 0
    for i in range(len(queries)):
        if len(got_ids[i]) != K:
            raise PhaseFailed(
                f"{name}: query {i} returned {len(got_ids[i])} results, "
                f"want {K}")
        hits += len(set(got_ids[i]) & set(want_ids[i].tolist()))
        rows = vecs[np.asarray(got_ids[i])]
        true = ((rows - queries[i][None]) ** 2).sum(1)
        got = np.asarray(got_dists[i], np.float32)
        if not np.all(np.isfinite(got)):
            raise PhaseFailed(f"{name}: query {i} non-finite distance {got}")
        e = row_eps * np.sqrt((rows ** 2).sum(1))
        tol = DIST_RTOL * np.maximum(true, 1e-3) + 2 * np.sqrt(true) * e + e * e
        bad = np.abs(got - true) > tol
        if bad.any():
            j = int(np.argmax(bad))
            raise PhaseFailed(
                f"{name}: query {i} rank {j} row {got_ids[i][j]} distance "
                f"{got[j]:.6g} vs float32 {true[j]:.6g} (tolerance "
                f"{tol[j]:.3g})")
    recall = hits / (len(queries) * K)
    if recall < RECALL_BAR:
        raise PhaseFailed(
            f"{name}: recall@{K} {recall:.4f} < {RECALL_BAR} against exact "
            f"float32 brute force")
    return recall


def _uuid(i: int) -> str:
    return str(uuid.UUID(int=i + 1))


def _row(u: str) -> int:
    return uuid.UUID(u).int - 1


# -- the run ------------------------------------------------------------------


class Smoke:
    """One run's state: the server, the data, and the report it fills."""

    def __init__(self, seed: int, rows: int, pq_rows: int,
                 expect_platform: str = "tpu"):
        self.seed, self.rows, self.pq_rows = seed, rows, min(pq_rows, rows)
        self.expect_platform = expect_platform
        self.t_end = time.monotonic() + OVERALL_LIMIT_S
        self.server: Server | None = None
        self.workdir: str | None = None
        self.report: dict = {
            "ok": False, "device": None, "rows": rows,
            "pq_rows": self.pq_rows, "dim": DIM, "k": K, "seed": seed,
            "note": "observations of one smoke run, not benchmark metrics",
            "phase_seconds": {}, "recall": {}, "kernels": {},
            "fallback_samples": {}, "native": {}, "failed_phase": None,
        }

    # each phase gets a deadline; every request inside it carries what is
    # left of that deadline as its own timeout, so a hung compile ends the
    # phase, not the tool's limit
    def phase(self, name: str, limit_s: float, fn):
        deadline = min(time.monotonic() + limit_s, self.t_end)
        t0 = time.monotonic()
        print(f"[{name}] ...", flush=True)
        try:
            out = fn(deadline)
        except PhaseFailed as e:
            self.report["failed_phase"] = name
            raise PhaseFailed(f"phase {name}: {e}") from None
        except Exception as e:  # noqa: BLE001 — every failure names its phase
            self.report["failed_phase"] = name
            late = time.monotonic() >= deadline
            raise PhaseFailed(
                f"phase {name}: {'timed out after' if late else 'failed at'} "
                f"{time.monotonic() - t0:.1f}s (limit {limit_s:.0f}s): "
                f"{type(e).__name__}: {e}") from None
        secs = time.monotonic() - t0
        self.report["phase_seconds"][name] = round(secs, 3)
        print(f"[{name}] ok in {secs:.1f}s", flush=True)
        return out

    @staticmethod
    def _left(deadline: float) -> float:
        left = deadline - time.monotonic()
        if left <= 0:
            raise TimeoutError("phase deadline passed")
        return left

    # -- phases ---------------------------------------------------------------

    def start(self, deadline: float) -> None:
        self.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        self.server = Server(self.workdir)
        self.server.wait_ready(deadline)
        line = next((ln for ln in self.server.log_text().splitlines()
                     if ln.startswith("weaviate-tpu ")), "")
        print(f"  server: {line}", flush=True)

    def identity(self, deadline: float) -> None:
        meta = _http("GET", self.server.base + "/v1/meta",
                     timeout=self._left(deadline))
        dev = meta.get("device")
        self.report["device"] = dev
        self.cache_dir = meta.get("compile_cache_dir")
        self.report["compile_cache"] = {
            "dir": self.cache_dir, "files_before": _count_files(self.cache_dir)}
        print(f"  device: {dev}  compile cache: {self.cache_dir}", flush=True)
        if not dev or dev.get("platform") != self.expect_platform:
            raise PhaseFailed(
                f"server runs on platform {dev and dev.get('platform')} "
                f"({dev}), want {self.expect_platform}: no accelerator, no "
                "result")

    def create_class(self, cls: str, index_type: str, deadline: float) -> None:
        _http("POST", self.server.base + "/v1/schema", {
            "class": cls, "vectorIndexType": index_type,
            "vectorIndexConfig": {"distance": "l2-squared"},
            "properties": [{"name": "bucket", "dataType": ["int"]}],
        }, timeout=self._left(deadline))

    def import_rows(self, cls: str, vecs: np.ndarray, deadline: float) -> float:
        """REST /v1/batch/objects from IMPORT_THREADS client threads.
        -> objects per second (an observation)."""
        url = self.server.base + "/v1/batch/objects"
        n = len(vecs)

        def send(lo: int) -> int:
            hi = min(lo + IMPORT_BATCH, n)
            objs = [{"class": cls, "id": _uuid(i),
                     "properties": {"bucket": i % FILTER_BUCKETS},
                     "vector": vecs[i].tolist()} for i in range(lo, hi)]
            res = _http("POST", url, {"objects": objs},
                        timeout=self._left(deadline))
            bad = [r for r in res if r["result"]["status"] != "SUCCESS"]
            if bad:
                raise PhaseFailed(f"batch at row {lo}: {bad[0]['result']}")
            return hi - lo

        t0 = time.monotonic()
        with ThreadPoolExecutor(IMPORT_THREADS) as pool:
            done = sum(pool.map(send, range(0, n, IMPORT_BATCH)))
        rate = done / (time.monotonic() - t0)
        live = self.index_health(cls, deadline)["live"]
        if done != n or live != n:
            raise PhaseFailed(f"{cls}: sent {done}, index holds {live}, "
                              f"want {n}")
        print(f"  {cls}: {n} x {DIM} rows in at {rate:.0f} objects/s "
              "(observation)", flush=True)
        return rate

    def index_health(self, cls: str, deadline: float) -> dict:
        dbg = _http("GET", self.server.base + "/debug/index",
                    timeout=self._left(deadline))
        shards = dbg["indexes"][cls]
        if len(shards) != 1:
            raise PhaseFailed(f"{cls}: {len(shards)} shards, want 1")
        return next(iter(shards.values()))["vector_index"]

    def _request(self, cls: str, q: np.ndarray, where: dict | None):
        from weaviate_tpu.grpcapi import weaviate_pb2 as pb

        req = pb.SearchRequest(
            class_name=cls, limit=K,
            near_vector=pb.NearVectorParams(vector=q.tolist()))
        if where is not None:
            req.where_json = json.dumps(where)
        return req

    @staticmethod
    def _parse(reply):
        if reply.error_message:
            raise PhaseFailed(f"slot error: {reply.error_message}")
        return ([_row(r.id) for r in reply.results],
                [r.distance for r in reply.results])

    def search_single(self, cls: str, queries, deadline: float):
        """One gRPC Search per query -> (ids, dists, first s, steady s)."""
        search, _ = self.server.grpc()
        ids, dists, secs = [], [], []
        for q in queries:
            t0 = time.monotonic()
            reply = search(self._request(cls, q, None),
                           timeout=self._left(deadline))
            secs.append(time.monotonic() - t0)
            i, d = self._parse(reply)
            ids.append(i)
            dists.append(d)
        return ids, dists, secs[0], float(np.median(secs[1:]))

    def search_batch(self, cls: str, queries, deadline: float,
                     where: dict | None = None, repeats: int = 3):
        """One gRPC BatchSearch of all queries, then `repeats` more ->
        (ids, dists, first s, steady s). Every repeat must answer alike."""
        from weaviate_tpu.grpcapi import weaviate_pb2 as pb

        _, batch = self.server.grpc()
        req = pb.BatchSearchRequest(
            requests=[self._request(cls, q, where) for q in queries])
        secs, first = [], None
        for _ in range(1 + repeats):
            t0 = time.monotonic()
            reply = batch(req, timeout=self._left(deadline))
            secs.append(time.monotonic() - t0)
            if len(reply.replies) != len(queries):
                raise PhaseFailed(f"{len(reply.replies)} replies for "
                                  f"{len(queries)} queries")
            parsed = [self._parse(r) for r in reply.replies]
            got = [p[0] for p in parsed]
            if first is None:
                first = parsed
            elif got != [p[0] for p in first]:
                raise PhaseFailed("a repeated BatchSearch answered differently")
        return ([p[0] for p in first], [p[1] for p in first], secs[0],
                float(np.median(secs[1:])))

    def _timed_query_set(self, name, cls, vecs, queries, want, deadline,
                         where=None, row_eps=0.0, single=False):
        if single:
            ids, dists, first, steady = self.search_single(
                cls, queries, deadline)
        else:
            ids, dists, first, steady = self.search_batch(
                cls, queries, deadline, where)
        recall = check_answers(name, vecs, queries, ids, dists, want, row_eps)
        self.report["recall"][name] = round(recall, 4)
        self._note_times(name, f"recall@{K} {recall:.4f}", first, steady)
        return ids

    def _note_times(self, name: str, what: str, first: float,
                    steady: float) -> None:
        self.report["phase_seconds"][name + ".first_query"] = round(first, 3)
        self.report["phase_seconds"][name + ".steady_query"] = round(steady, 4)
        print(f"  {name}: {what}; first {first:.2f}s, steady "
              f"{steady * 1000:.1f} ms (observations)", flush=True)

    def enable_pq(self, cls: str, pq: dict, deadline: float) -> None:
        """The reference's own procedure: import, then switch pq on with a
        class update — the fit and encode run inside this one request."""
        body = _http("GET", f"{self.server.base}/v1/schema/{cls}",
                     timeout=self._left(deadline))
        body["vectorIndexConfig"]["pq"] = pq
        _http("PUT", f"{self.server.base}/v1/schema/{cls}", body,
              timeout=self._left(deadline))
        h = self.index_health(cls, deadline)
        if not h["compressed"]:
            raise PhaseFailed(f"{cls}: not compressed after pq update: "
                              f"{h['pq']}")

    def kernels_ok(self, cls: str, kernel: str, deadline: float) -> dict:
        """>= 1 validated and 0 rejected compiled shapes for `kernel`."""
        k = self.index_health(cls, deadline)["kernels"][kernel]
        self.report["kernels"][f"{cls}.{kernel}"] = k
        if k["validated"] < 1 or k["rejected"] != 0 or k["broken"]:
            raise PhaseFailed(
                f"{cls}: kernel {kernel} validated={k['validated']} "
                f"rejected={k['rejected']} broken={k['broken']} "
                f"(rejected shapes {k['rejected_shapes']}) — the answers "
                "came from a fallback tier")
        print(f"  {cls}: {kernel} validated shapes {k['validated_shapes']}",
              flush=True)
        return k

    def final_checks(self, deadline: float) -> None:
        from prometheus_client.parser import text_string_to_metric_families

        text = _http("GET", f"http://127.0.0.1:{self.server.metrics_port}"
                            "/metrics", timeout=self._left(deadline))
        breaker = None
        for family in text_string_to_metric_families(text):
            for sample in family.samples:
                if sample.name == "weaviate_device_fallback_total" \
                        and sample.value > 0:
                    self.report["fallback_samples"][
                        json.dumps(sample.labels, sort_keys=True)] = sample.value
                elif sample.name == "weaviate_breaker_state":
                    breaker = sample.value
        self.report["breaker_state"] = breaker
        meta = _http("GET", self.server.base + "/v1/meta",
                     timeout=self._left(deadline))
        self.report["native"] = meta["native"]
        print(f"  native libraries: {meta['native']}", flush=True)
        if self.report["fallback_samples"]:
            raise PhaseFailed("answered by a fallback plane: "
                              f"{self.report['fallback_samples']}")
        if breaker != 0.0:
            raise PhaseFailed(f"breaker state {breaker}, want 0 (closed)")
        failed = {k: v for k, v in meta["native"].items()
                  if v.startswith("build_failed")}
        if failed:
            raise PhaseFailed(f"native build failed: {failed}")

    def shutdown(self, deadline: float) -> None:
        rc = self.server.stop(self._left(deadline))
        log = self.server.log_text()
        if rc != 0 or "shutdown complete" not in log:
            raise PhaseFailed(f"server exit rc={rc}, 'shutdown complete' "
                              f"{'seen' if 'shutdown complete' in log else 'missing'}"
                              f":\n{log[-2000:]}")

    # -- the sequence ---------------------------------------------------------

    def run(self) -> None:
        self.phase("start", 240, self.start)
        self.phase("identity", 30, self.identity)
        ndev = self.report["device"]["count"]

        def reference(deadline):
            vecs, q1, qb = make_dataset(self.seed, self.rows)
            allow = np.flatnonzero(
                np.arange(self.rows) % FILTER_BUCKETS == 3)
            qf = qb[:N_FILTERED]
            print(f"  filter keeps {allow.size} of {self.rows} rows",
                  flush=True)
            return (vecs, q1, qb, qf, exact_topk(vecs, q1, K),
                    exact_topk(vecs, qb, K), exact_topk(vecs, qf, K, allow))
        vecs, q1, qb, qf, want1, wantb, wantf = self.phase(
            "reference", 240, reference)
        where = {"path": ["bucket"], "operator": "Equal", "valueInt": 3}

        def import_main(deadline):
            self.create_class("Smoke", "hnsw_tpu", deadline)
            self.report["import_objects_per_s"] = round(
                self.import_rows("Smoke", vecs, deadline))
        self.phase("import", 560, import_main)
        self.phase("search_b1", 150, lambda d: self._timed_query_set(
            "search_b1", "Smoke", vecs, q1, want1, d, single=True))
        ids_b = self.phase("batch256", 150, lambda d: self._timed_query_set(
            "batch256", "Smoke", vecs, qb, wantb, d))
        self.phase("filtered", 150, lambda d: self._timed_query_set(
            "filtered", "Smoke", vecs, qf, wantf, d, where=where))
        self.phase("kernels_gmin", 30,
                   lambda d: self.kernels_ok("Smoke", "gmin", d))

        # compressed tiers: the first pq_rows rows, 256-wide BatchSearch
        pvecs = vecs[:self.pq_rows]
        rng = np.random.default_rng(self.seed + 1)
        self_rows = rng.choice(self.pq_rows, BATCH, replace=False)
        q_self = pvecs[self_rows] + 0.001 * rng.standard_normal(
            (BATCH, DIM), dtype=np.float32)

        def import_pq(cls):
            def fn(deadline):
                self.create_class(cls, "hnsw_tpu", deadline)
                self.import_rows(cls, pvecs, deadline)
            return fn

        self.phase("pq8_import", 200, import_pq("SmokePq8"))
        self.phase("pq8_fit", 200, lambda d: self.enable_pq(
            "SmokePq8", {"enabled": True, "segments": 32, "rescore": False},
            d))

        def pq8_query(deadline):
            # raw ADC recall is low by design: the check is that each
            # stored row, queried with small noise, comes back first
            ids, _, first, steady = self.search_batch(
                "SmokePq8", q_self, deadline)
            miss = [int(r) for r, got in zip(self_rows, ids)
                    if not got or got[0] != r]
            self.report["recall"]["pq8_self_rank1"] = round(
                1.0 - len(miss) / BATCH, 4)
            self._note_times(
                "pq8_query",
                f"own id at rank 1 for {BATCH - len(miss)}/{BATCH}", first,
                steady)
            if miss:
                raise PhaseFailed(f"rows {miss[:8]} did not come back first")
            self.kernels_ok("SmokePq8", "pq_gmin", deadline)
        self.phase("pq8_query", 150, pq8_query)

        self.phase("pq4_import", 200, import_pq("SmokePq4"))
        self.phase("pq4_fit", 250, lambda d: self.enable_pq(
            "SmokePq4", {"enabled": True, "segments": 32, "bits": 4}, d))

        def pq4_query(deadline):
            want4 = exact_topk(pvecs, qb, K)
            self._timed_query_set("pq4_funnel", "SmokePq4", pvecs, qb, want4,
                                  deadline, row_eps=BF16_EPS)
            k4 = self.kernels_ok("SmokePq4", "pq4", deadline)
            stage1 = ("pallas" if k4["stage1_byte_lut_dispatches"] == 0
                      and k4["stage1_pallas_dispatches"] > 0 else "byte_lut")
            self.report["pq4_stage1"] = stage1
            print(f"  pq4: stage 1 ran the {stage1} scan "
                  f"({k4['stage1_pallas_dispatches']} pallas, "
                  f"{k4['stage1_byte_lut_dispatches']} byte-LUT dispatches)",
                  flush=True)
            if stage1 != "pallas":
                raise PhaseFailed("funnel stage 1 ran the byte-LUT scan, "
                                  "not the Pallas kernel")
        self.phase("pq4_query", 150, pq4_query)

        if ndev > 1:
            self.run_mesh(ndev, vecs, qb, wantb, ids_b)

        self.phase("final_checks", 30, self.final_checks)
        self.phase("shutdown", 150, self.shutdown)
        self.report["compile_cache"]["files_after"] = _count_files(
            self.cache_dir)

    def run_mesh(self, ndev: int, vecs, qb, want, ids_one_chip) -> None:
        """The same rows in an `hnsw_tpu_mesh` class over every device: held
        to the same reference, compared with the one-chip class's ids, each
        device holding about 1/ndev of the slab."""
        def import_mesh(deadline):
            self.create_class("SmokeMesh", "hnsw_tpu_mesh", deadline)
            self.import_rows("SmokeMesh", vecs, deadline)
        self.phase("mesh_import", 560, import_mesh)

        def mesh_query(deadline):
            ids = self._timed_query_set("mesh256", "SmokeMesh", vecs, qb,
                                        want, deadline)
            # selection is approximate on the chip (lax.approx_min_k over
            # 1/ndev of the columns per device, over all of them on one
            # chip), so a few id lists may differ where both pass the
            # recall bar; a merge that mapped rows wrongly would differ on
            # most of them
            same = sum(a == b for a, b in zip(ids, ids_one_chip))
            h = self.index_health("SmokeMesh", deadline)
            per_dev = h["per_device"]
            self.report["mesh"] = {
                "devices": h["devices"], "queries": len(qb),
                "queries_equal_to_one_chip": same, "per_device": per_dev}
            print(f"  mesh: {h['devices']} devices; {same}/{len(qb)} id "
                  f"lists equal to the one-chip class's; per device "
                  f"{per_dev}", flush=True)
            if h["devices"] != ndev:
                raise PhaseFailed(f"mesh spans {h['devices']} devices, "
                                  f"server has {ndev}")
            if same < 0.95 * len(qb):
                raise PhaseFailed(
                    f"only {same} of {len(qb)} id lists equal the one-chip "
                    "class's")
            share = self.rows / ndev
            for d in per_dev:
                if abs(d["rows"] - share) > 0.1 * share:
                    raise PhaseFailed(f"device {d['device']} holds "
                                      f"{d['rows']} rows, want ~{share:.0f}")
            used = [d["allocator_bytes_in_use"] for d in per_dev]
            if all(u is not None for u in used):
                # the one-chip classes all live on device 0, so hold the
                # other devices to each other, and every device to at
                # least its analytic share of the slab
                rest = used[1:]
                if max(rest) > 1.25 * min(rest) or \
                        min(used) < per_dev[0]["slab_bytes"]:
                    raise PhaseFailed(f"allocator bytes per device {used} "
                                      "are not an even split of the slab")
            self.kernels_ok("SmokeMesh", "gmin", deadline)
        self.phase("mesh_query", 200, mesh_query)


def _count_files(path) -> int | None:
    if not path or not os.path.isdir(path):
        return 0 if path else None
    return sum(len(files) for _, _, files in os.walk(path))


def run(seed: int = 0, rows: int = ROWS, pq_rows: int = PQ_ROWS,
        expect_platform: str = "tpu") -> dict:
    """Run every phase -> the report (report["ok"] says whether all passed).
    The server child is always stopped."""
    smoke = Smoke(seed, rows, pq_rows, expect_platform)
    try:
        smoke.run()
        smoke.report["ok"] = True
    except PhaseFailed as e:
        smoke.report["error"] = str(e)
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        if smoke.server is not None:
            print("--- server log tail ---\n"
                  + smoke.server.log_text()[-4000:], file=sys.stderr,
                  flush=True)
    finally:
        if smoke.server is not None:
            smoke.server.kill()
        if smoke.workdir:
            shutil.rmtree(smoke.workdir, ignore_errors=True)
    return smoke.report


def result_line(report: dict) -> dict:
    """The one object the driver reads from the last line of stdout: exactly
    `ok` and the device as the server's JAX reported it. Everything else the
    run observed goes on the `observations:` line before it."""
    dev = report["device"]
    return {"ok": bool(report["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["device_kind"]),
                       "count": int(dev["count"])}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="rows of the main class (a cut of scale; printed)")
    ap.add_argument("--pq-rows", type=int, default=PQ_ROWS,
                    help="rows of each compressed-tier class")
    args = ap.parse_args(argv)
    report = run(args.seed, args.rows, args.pq_rows)
    if not report["ok"]:
        # no result line without an accelerator or with a failed phase
        print(json.dumps(report), file=sys.stderr)
        return 1
    print("observations: " + json.dumps(report), flush=True)
    print(json.dumps(result_line(report)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
