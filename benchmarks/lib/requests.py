"""Requests as a client makes them, from a traffic file's parameters.

One builder serves every mix: `request` (Search | BatchSearch), `width`
(queries in a BatchSearch), `limit`, `class` (another class than the
configuration's), `write_share` (that share of requests is a REST batch
write that puts `write_batch` stored rows again, with the properties the
configuration's dataset gives them, unchanged: the write path runs and the
exact answers stay what they were), `timeout_s` (the request's deadline).
Every pool query goes out with its own filter (`filters`, one GraphQL-grammar
`where` or None a pool query, sent as `where_json` in a Search and in every
slot of a BatchSearch): the filter plan the traffic names (`where`, one
constant filter; `filter_plan`, a plan of the dataset's), resolved by
benchmarks/build.py `plan_filters`.
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.lib import data as gen
from benchmarks.lib.server import http, stubs


class Request:
    __slots__ = ("kind", "msg", "qidx")

    def __init__(self, kind: str, msg, qidx: np.ndarray):
        self.kind, self.msg, self.qidx = kind, msg, qidx

    @property
    def queries(self) -> int:
        return len(self.qidx)


class RequestBuilder:
    def __init__(self, cfg: dict, traffic: dict, pool: np.ndarray,
                 filters: list, rows=None, dataset=None):
        from weaviate_tpu.grpcapi import weaviate_pb2 as pb

        self.pb = pb
        self.cls = traffic.get("class") or cfg["class"]["class"]
        self.limit = int(traffic.get("limit") or cfg["k"])
        self.width = int(traffic.get("width", 1))
        self.kind = {"Search": "search", "BatchSearch": "batch"}[
            traffic.get("request", "Search")]
        if self.kind == "search" and self.width != 1:
            raise ValueError("a Search carries one query: width must be 1")
        if len(filters) != len(pool):
            raise ValueError(f"{len(filters)} filters for {len(pool)} queries")
        self.where_json = [json.dumps(w) if w else "" for w in filters]
        self.write_share = float(traffic.get("write_share", 0.0))
        self.write_batch = int(traffic.get("write_batch", 100))
        self.cfg, self.dataset = cfg, dataset
        self.pool, self.rows = pool, rows
        self._pool_lists = None

    def _search(self, q: int):
        if self._pool_lists is None:
            self._pool_lists = [v.tolist() for v in self.pool]
        req = self.pb.SearchRequest(
            class_name=self.cls, limit=self.limit,
            near_vector=self.pb.NearVectorParams(vector=self._pool_lists[q]))
        if self.where_json[q]:
            req.where_json = self.where_json[q]
        return req

    def draw(self, rng: np.random.Generator) -> Request:
        """The next request of the mix, from the seed's stream."""
        if self.write_share > 0.0 and rng.random() < self.write_share:
            ids = np.unique(rng.integers(0, self.rows.shape[0],
                                         self.write_batch))
            props = self.dataset.properties(self.cfg, ids)
            objs = [{"class": self.cls, "id": gen.uuid_of(int(i)),
                     "properties": p,
                     "vector": np.asarray(self.rows[int(i)]).tolist()}
                    for i, p in zip(ids, props)]
            return Request("write", {"objects": objs}, np.empty(0, np.int64))
        qidx = rng.integers(0, len(self.pool), self.width)
        if self.kind == "search":
            return Request("search", self._search(int(qidx[0])), qidx)
        return Request("batch", self.pb.BatchSearchRequest(
            requests=[self._search(int(q)) for q in qidx]), qidx)


class Caller:
    """One client connection: its own gRPC channel. `call` blocks; `submit`
    returns at once and runs `done(reply_or_exception, t_done)` later."""

    def __init__(self, server, timeout_s: float):
        self.server, self.timeout_s = server, timeout_s
        self.channel = server.channel()
        import grpc

        grpc.channel_ready_future(self.channel).result(timeout=30.0)
        self.search, self.batch = stubs(self.channel)
        self._writes = None

    def _write(self, body):
        res = http("POST", self.server.base + "/v1/batch/objects", body,
                   timeout=self.timeout_s)
        bad = [r for r in res if r["result"]["status"] != "SUCCESS"]
        if bad:
            raise RuntimeError(f"batch write: {bad[0]['result']}")
        return res

    def call(self, req: Request):
        if req.kind == "write":
            return self._write(req.msg)
        stub = self.search if req.kind == "search" else self.batch
        return stub(req.msg, timeout=self.timeout_s)

    def submit(self, req: Request, done) -> None:
        if req.kind == "write":
            if self._writes is None:
                self._writes = ThreadPoolExecutor(2)
            fut = self._writes.submit(self._write, req.msg)
        else:
            stub = self.search if req.kind == "search" else self.batch
            fut = stub.future(req.msg, timeout=self.timeout_s)

        def finished(f):
            t = time.monotonic()
            try:
                done(f.result(), t)
            except Exception as e:  # noqa: BLE001 — a failed request, counted
                done(e, t)
        fut.add_done_callback(finished)

    def close(self) -> None:
        if self._writes is not None:
            self._writes.shutdown(wait=True)
        self.channel.close()


def parse_reply(req: Request, reply, k: int):
    """-> (ids [Q, k] int64 padded with -1, dists [Q, k] f32 padded with
    nan, error or None) of one answered request."""
    nq = req.queries
    ids = np.full((nq, k), -1, np.int64)
    dists = np.full((nq, k), np.nan, np.float32)
    if req.kind == "write":
        return ids, dists, None
    replies = [reply] if req.kind == "search" else list(reply.replies)
    if len(replies) != nq:
        return ids, dists, f"{len(replies)} replies for {nq} queries"
    for i, r in enumerate(replies):
        if r.error_message:
            return ids, dists, f"slot error: {r.error_message}"
        res = r.results
        if len(res) > k:
            return ids, dists, f"{len(res)} results, want {k}"
        for j, x in enumerate(res):
            ids[i, j] = gen.row_of(x.id)
            dists[i, j] = x.distance
    return ids, dists, None
