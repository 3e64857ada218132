"""The coalescer as the narrow requests' default path (PR 44).

1. the default configuration builds a coalescer with no window, and a single
   gRPC `Search` through it answers what the direct path answers, bit for
   bit;
2. with no window a lone request is dispatched at once and riders that
   arrive behind a dispatch in flight share the next one: a lane waits for
   the dispatch in front of it, never for a clock (a fake shard whose
   finalize blocks on an event; no sleeps); a lone request is served on its
   own thread only inside `submit`, for a caller that waits at once: one
   thread never holds the in-flight slot while it waits on another lane (a
   mixed `BatchSearch` beside a single `Search`);
3. a lane closes where the plan would change programs: over a tiled layout
   at the widest width `plan_search` still probes (`same_program_width`),
   so a lane of 20 riders leaves as probed dispatches whose answers are the
   one-query probed answers;
4. wide requests keep their own paths whatever the coalescer is: a wide
   filtered `BatchSearch` is one group, a wide GraphQL batch is no pool task
   a slot;
5. the programs a lane can take are loaded or compiled by the first
   request of a depth on an index state, before any lane needs them;
6. the accounts at width above one: a probed plan's `rows` is what its
   program reads, `/debug/perf` carries the queue's counters, a rider's wait
   is an interval on its own thread.
"""

import json
import threading
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config, load_config
from weaviate_tpu.config.config import CoalescerConfig, IvfConfig
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.index import tpu
from weaviate_tpu.index.plan import (PlanView, plan_search,
                                     probed_reads_less, same_program_width)
from weaviate_tpu.monitoring import perf, tracing
from weaviate_tpu.serving import controller
from weaviate_tpu.serving.coalescer import QueryCoalescer
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 400, 16, 5


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tpu.set_ivf_config(None)
    tracing.configure(None)
    perf.configure(None)


def _app(tmp_path, cls="Co", n=N, vecs=None, tweak=None, data="data"):
    from weaviate_tpu.server import App

    cfg = Config()
    if tweak is not None:
        tweak(cfg)
    app = App(config=cfg, data_path=str(tmp_path / data))
    if app.schema.get_class(cls) is None:
        app.schema.add_class({
            "class": cls, "vectorIndexType": "hnsw_tpu",
            "vectorIndexConfig": {"distance": "l2-squared"},
            "properties": [{"name": "tag", "dataType": ["int"]}]})
    if vecs is None:
        # small integers: every distance is exact in float32 whatever the
        # order of accumulation (tests/test_coalescer.py)
        vecs = np.random.default_rng(44).integers(
            -8, 8, (n, DIM)).astype(np.float32)
    idx = app.db.get_index(cls)
    if idx.object_count() == 0:
        idx.put_batch([
            StorObj(class_name=cls, uuid=str(uuidlib.UUID(int=i + 1)),
                    properties={"tag": i % 7}, vector=vecs[i])
            for i in range(len(vecs))])
    return app, idx, vecs


def _rows(results):
    return [(r.obj.uuid, r.distance) for r in results]


def _threads(n, fn):
    barrier = threading.Barrier(n)

    def run(i):
        barrier.wait()
        fn(i)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive()


# -- 1. the default ------------------------------------------------------------


def test_the_defaults_are_on_and_without_a_window():
    assert CoalescerConfig().enabled is True
    assert CoalescerConfig().window_ms == 0.0
    cfg = load_config({})
    assert (cfg.coalescer.enabled, cfg.coalescer.window_ms,
            cfg.coalescer.pipeline_depth) == (True, 0.0, 1)
    co = QueryCoalescer()
    try:
        assert co.window_s == 0.0
        # what is derived from the window keeps a floor at 0
        assert co._sig_ttl == 1.0
        # the controller's lease reads the default through while it is off
        assert controller.coalescer_window_s(co.window_s) == 0.0
    finally:
        co.shutdown()


def test_a_default_app_answers_a_grpc_search_as_the_direct_path_does(
        tmp_path):
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient

    app, idx, vecs = _app(tmp_path)
    srv = GrpcServer(app, port=0, max_workers=4)
    srv.start()
    cl = SearchClient(f"127.0.0.1:{srv.port}")
    try:
        assert app.coalescer is not None and app.coalescer.window_s == 0.0
        assert app.explorer.coalescer is app.coalescer
        for i in (3, 17, 251):
            q = vecs[i] + np.float32(0.5)
            want = _rows(idx.object_vector_search(q, K)[0])
            rep = cl.search(pb.SearchRequest(
                class_name="Co", limit=K,
                near_vector=pb.NearVectorParams(vector=q.tolist())))
            assert [(r.id, r.distance) for r in rep.results] == want
        st = app.coalescer.stats()
        # each rode a lane of its own: nobody to wait for, nothing shed
        assert st["dispatches"] == st["requests"] == 3
        assert st["shed"] == {} and st["bypass"] == {}
    finally:
        cl.close()
        srv.stop()
        app.shutdown()


# -- 2. a lane waits for the dispatch in front of it, never for a clock --------


class _GatedShard:
    """Enqueues at once; the FIRST dispatch's finalize blocks on `gate`
    (after `free` dispatches that are not counted: a depth's first request
    makes the lane that brings the wider programs, and is never led)."""

    class _Index:
        metric = "l2-squared"

        def search_by_vectors_async(self, *a, **kw):  # marks true async
            raise AssertionError("the shard is asked, not the index")

    def __init__(self, free=0):
        self.vector_index = self._Index()
        self.class_def = None
        self.free = free
        self.widths: list = []
        self.threads: list = []
        self.gate = threading.Event()
        self.first_fetching = threading.Event()
        self.second_enqueued = threading.Event()

    def object_vector_search_async(self, q, k, include_vector=False,
                                   flt=None):
        if self.free:
            self.free -= 1
            return lambda: [[float(v[0])] for v in q]
        n = len(self.widths)
        self.widths.append(int(q.shape[0]))
        self.threads.append(threading.current_thread())
        if n == 1:
            self.second_enqueued.set()

        def done():
            if n == 0:
                self.first_fetching.set()
                assert self.gate.wait(30)
            return [[float(v[0])] for v in q]

        return done


def test_a_lone_request_leaves_at_once_and_riders_behind_a_dispatch_share():
    co = QueryCoalescer()   # the defaults: no window, depth 1
    shard = _GatedShard(free=1)
    vec = lambda x: np.full((1, 4), x, np.float32)  # noqa: E731
    assert co.submit(shard, vec(0), K, wait_now=True)() == [[0.0]]
    notified = []
    notify = co._cv.notify
    co._cv.notify = lambda *a: (notified.append(1), notify(*a))[1]
    first = []
    try:
        # nobody else is there, no clock runs and the caller waits at once:
        # the request serves its lane of one on ITS OWN thread, inside
        # `submit` (no flusher, no pool, no wake-up) ...
        leader = threading.Thread(target=lambda: first.append(
            co.submit(shard, vec(1), K, wait_now=True)()))
        leader.start()
        assert shard.first_fetching.wait(30)
        assert shard.widths == [1] and notified == []
        assert shard.threads == [leader]
        # ... and holds the in-flight slot meanwhile: the next arrival's
        # program is enqueued behind it, and the flusher then waits for the
        # dispatch in front to finalize ...
        w2 = co.submit(shard, vec(2), K)
        assert shard.second_enqueued.wait(30)
        # ... so whoever arrives meanwhile gathers in ONE lane
        later = [co.submit(shard, vec(x), K) for x in (3, 4, 5)]
        assert shard.widths == [1, 1]
        shard.gate.set()
        leader.join(timeout=30)
        assert first == [[[1.0]]]
        assert [w()[0] for w in (w2, *later)] == [
            [2.0], [3.0], [4.0], [5.0]]
        assert shard.widths == [1, 1, 3]
        st = co.stats()
        assert (st["dispatches"], st["requests"]) == (4, 6)
        # the flusher was woken once a queued LANE, not once a request
        assert len(notified) == 2
        # the pipeline is idle again: the next lone request leads again,
        # and is settled when `submit` returns
        me = threading.current_thread()
        done = co.submit(shard, vec(6), K, wait_now=True)
        assert shard.widths == [1, 1, 3, 1] and shard.threads[-1] is me
        assert done() == [[6.0]] and len(notified) == 2
        # a caller that defers its callable is never served on its own
        # thread: its lane is queued and the flusher's, whoever is there
        done = co.submit(shard, vec(7), K)
        assert done() == [[7.0]] and len(notified) == 3
        assert shard.threads[-1] is not me
    finally:
        shard.gate.set()
        co.shutdown()


def test_a_lane_is_led_only_where_no_window_holds_it_and_fails_as_a_lane():
    class _Broken(_GatedShard):
        def object_vector_search_async(self, q, k, include_vector=False,
                                       flt=None):
            raise RuntimeError("device")

    me = threading.current_thread()
    one = np.ones((1, 4), np.float32)
    held = QueryCoalescer(window_s=0.2)
    shard = _GatedShard()
    shard.gate.set()
    try:
        # a window: the lane is held for company, the flusher dispatches it
        for _ in range(2):
            assert held.submit(shard, one, K, wait_now=True)() == [[1.0]]
            assert shard.threads[-1] is not me
    finally:
        held.shutdown()
    co = QueryCoalescer()
    try:
        # a depth's first lane is the flusher's (its maker warms meanwhile)
        assert co.submit(shard, one, K, wait_now=True)() == [[1.0]]
        assert shard.threads[-1] is not me
        # a led lane's failure is the waiter's to raise, not `submit`'s
        broken = _Broken()
        co._warmed[broken] = {(K, 256)}
        lead = co.submit(broken, one, K, wait_now=True)
        with pytest.raises(RuntimeError, match="device"):
            lead()
        # the slot came back: the next request leads and answers
        assert co.submit(shard, one, K, wait_now=True)() == [[1.0]]
        assert shard.threads[-1] is me
        assert co.stats()["dispatches"] == 2
    finally:
        co.shutdown()


def test_no_thread_holds_the_slot_while_it_waits_on_another_lane(tmp_path):
    """A legal mixed `BatchSearch` (an unfiltered group, then ONE filtered
    slot under a hot signature, then a ragged group that falls back slot by
    slot) defers its groups' `done()` to the end and waits on other lanes
    before it: were its first group served as a lead that holds the
    in-flight slot until that `done()`, the flusher could never take the
    slot for the lanes in between and the thread would wait on itself for
    `waiter_timeout_s`, with every other client's request behind it."""
    app, idx, vecs = _app(tmp_path)
    co = app.coalescer
    co.waiter_timeout_s = 20.0     # the test fails long before, see below
    flt = {"path": ["tag"], "operator": "Equal", "valueInt": 3}
    near = lambda v: {"vector": v.tolist()}  # noqa: E731
    try:
        from weaviate_tpu.entities.filters import LocalFilter
        batch = [
            GetParams(class_name="Co", near_vector=near(vecs[2]), limit=10),
            GetParams(class_name="Co", near_vector=near(vecs[3]), limit=K,
                      filters=LocalFilter.from_dict(flt)),
            # a ragged group (two widths under one key): np.stack fails
            # and its slots go one by one
            GetParams(class_name="Co", near_vector=near(vecs[4]), limit=7),
            GetParams(class_name="Co", near_vector=near(vecs[5][:8]),
                      limit=7),
        ]
        want = [idx.object_vector_search(vecs[2], 10)[0],
                idx.object_vector_search(
                    vecs[3], K, flt=LocalFilter.from_dict(flt))[0],
                idx.object_vector_search(vecs[4], 7)[0]]
        # the depths have met their first request (it brings programs, for
        # seconds), and the signature is hot: the filtered slot rides its
        # lane
        for k in (10, 7, K):
            app.traverser.get_class(GetParams(
                class_name="Co", near_vector=near(vecs[1]), limit=k))
        app.traverser.get_class(GetParams(
            class_name="Co", near_vector=near(vecs[1]), limit=K,
            filters=LocalFilter.from_dict(flt)))
        before = co.stats()
        out, single = [], []
        import time
        t0 = time.monotonic()
        batcher = threading.Thread(
            target=lambda: out.extend(app.explorer.get_class_batched(batch)))
        batcher.start()
        # a single `Search` of another client beside it is not stalled
        single.append(app.traverser.get_class(GetParams(
            class_name="Co", near_vector=near(vecs[6]), limit=K)))
        batcher.join(timeout=60)
        took = time.monotonic() - t0
        assert not batcher.is_alive() and len(out) == 4
        for got, exp in zip(out[:3], want):
            assert _rows(got) == _rows(exp)
        assert isinstance(out[3], Exception)        # its own slot only
        assert _rows(single[0]) == _rows(
            idx.object_vector_search(vecs[6], K)[0])
        st = co.stats()
        # nobody sat out a liveness bound, nothing was shed or retried
        assert took < co.waiter_timeout_s / 2
        assert st["shed"] == {} and st["bypass"] == before["bypass"]
        # the filtered slot did ride its signature's lane
        assert st["requests"] - before["requests"] == 4
    finally:
        app.shutdown()


# -- 3. a lane closes where the plan would change programs ---------------------


def _view(n, nlist, cap_p, top_p, gathered=False, probe=True):
    class _Cfg:
        flat_search_cutoff = 40000
        exact_topk = True

    class _Programs:
        def kernel_serves(self, *shape):
            return False

    class _Kernels:
        _gmin_broken = False

    return PlanView(
        config=_Cfg(), metric="cosine", programs=_Programs(),
        kernels=_Kernels(), component="test", n=n, live=n, dim=768,
        ndev=1, slab=n, fill=n, itemsize=4, compressed=False,
        ivf_meta=(nlist, cap_p) if probe else None,
        ivf_probe=(lambda k: (top_p, 0)) if probe else None,
        ivf_gathered=gathered)


def test_the_widest_probed_width_of_the_cells_state_is_16():
    """`cohere-768-cos-ivf`: 4,096 tiles of 352 slots, 64 probed."""
    widths = [w for w in tpu._B_BUCKETS if w <= 256]
    view = _view(4096 * 352, 4096, 352, 64)
    assert same_program_width(view, 10, widths) == 16
    # and it is the rung where `plan_search` changes programs
    assert plan_search(view, 16, 16, 10).ivf is not None
    assert plan_search(view, 17, 64, 10).ivf_declined
    # no layout, or a probe that is off: every width runs the same program
    flat = _view(4096 * 352, 4096, 352, 64, probe=False)
    assert same_program_width(flat, 10, widths) is None
    # a layout declined at one query already is the flat program's at all
    tiny = _view(2000, 8, 250, 8)
    assert not probed_reads_less(1, 1, 8, 250, 8, 2000)
    assert same_program_width(tiny, 10, widths) is None
    # a bucket table pays the gathered price and closes sooner
    assert same_program_width(
        _view(4096 * 352, 4096, 352, 64, gathered=True), 10, widths) == 4


def _ivf_on(cfg):
    cfg.ivf = IvfConfig(enabled=True, nlist=64, min_n=256, top_p=2,
                        train_sample=4096, train_iters=4)
    # a lane leaves on its width alone: what closes it is under test
    cfg.coalescer.window_ms = 30_000.0


def test_over_a_tiled_layout_twenty_riders_leave_as_probed_dispatches(
        tmp_path):
    rng = np.random.default_rng(5)
    centres = rng.standard_normal((64, DIM)).astype(np.float32) * 4
    vecs = (centres[rng.integers(0, 64, 4000)]
            + 0.5 * rng.standard_normal((4000, DIM)).astype(np.float32))
    app, idx, _ = _app(tmp_path, vecs=vecs, tweak=_ivf_on)
    try:
        shard = idx.single_local_shard()
        vidx = shard.vector_index
        vidx.flush()
        assert vidx._ivf_tiled
        nlist, cap_p, _ = vidx._ivf_meta
        # 2.8 x b x 2 x cap_p + 64 < 64 x cap_p holds at 1 and 4, not at 16
        assert probed_reads_less(4, 1, 2, cap_p, nlist, vidx.n)
        assert not probed_reads_less(16, 1, 2, cap_p, nlist, vidx.n)
        assert vidx.lane_width(K, 256) == 4
        assert app.coalescer._lane_width(shard, K) == 4
        queries = [vecs[i] + np.float32(0.01) for i in range(20)]
        # the one-query probed answers, on the direct path
        want = []
        for q in queries:
            h = vidx.search_by_vectors_async(q[None], K)
            assert h.plan.ivf is not None
            want.append(h())
        got = [None] * 20

        def ask(i):
            got[i] = app.traverser.get_class(GetParams(
                class_name="Co", near_vector={"vector": queries[i].tolist()},
                limit=K))

        _threads(20, ask)
        for res, (ids, dists) in zip(got, want):
            assert [uuidlib.UUID(r.obj.uuid).int - 1 for r in res] \
                == ids[0].tolist()
            np.testing.assert_array_equal(
                np.array([r.distance for r in res], np.float32), dists[0])
        st = app.coalescer.stats()
        # five lanes of four: none as wide as the flat program's widths
        assert (st["dispatches"], st["requests"], st["rows"]) == (5, 20, 20)
        assert vidx.scan_programs.ivf_declined == 0
        # a request wider than a lane leaves alone, as on the direct path
        wide = app.coalescer.submit(shard, vecs[:8], K)()
        assert [_rows(r) for r in wide] == [
            _rows(r) for r in shard.object_vector_search(vecs[:8], K)]
        st = app.coalescer.stats()
        assert (st["dispatches"], st["rows"]) == (6, 28)
        assert st["bypass"] == {}
    finally:
        app.shutdown()


def test_a_flat_index_keeps_the_configured_width(tmp_path):
    app, idx, vecs = _app(tmp_path)
    try:
        shard = idx.single_local_shard()
        assert shard.vector_index.lane_width(K, 256) == 256
        assert app.coalescer._lane_width(shard, K) == 256
    finally:
        app.shutdown()


# -- 4. wide requests keep their own paths -------------------------------------


def _traced(cfg):
    cfg.tracing.enabled = True
    cfg.tracing.sample_rate = 1.0


def test_a_wide_filtered_batch_is_one_group_with_the_coalescer_on(tmp_path):
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import SearchServicer

    class Ctx:
        def abort(self, *a):
            raise AssertionError(a)

        def invocation_metadata(self):
            return ()

        def set_trailing_metadata(self, *a):
            pass

    app, idx, vecs = _app(tmp_path, tweak=_traced)
    try:
        assert app.coalescer is not None
        reqs = [pb.SearchRequest(
            class_name="Co", limit=K,
            where_json=json.dumps({"path": ["tag"], "operator": "Equal",
                                   "valueInt": i % 7}),
            near_vector=pb.NearVectorParams(
                vector=(vecs[i] + np.float32(0.5)).tolist()))
            for i in range(256)]
        got = SearchServicer(app).BatchSearch(
            pb.BatchSearchRequest(requests=reqs), Ctx())
        reply = pb.BatchSearchReply.FromString(
            got if isinstance(got, (bytes, bytearray))
            else got.SerializeToString())
        assert len(reply.replies) == 256
        for i, one in enumerate(reply.replies):
            assert not one.error_message and len(one.results) == K
            tags = {(uuidlib.UUID(r.id).int - 1) % 7 for r in one.results}
            assert tags == {i % 7}
        s = perf.get_window().summary()
        # what `filtered_queries_per_dispatch` reads: rows / dispatches
        assert s["rows"] == 256 and s["rows"] / s["dispatches"] > 1
        assert s["group_inputs"]["groups"] == 1
        # no slot went through a lane, and none was counted as a bypass
        st = app.coalescer.stats()
        assert st["dispatches"] == 0 and st["bypass"] == {}
    finally:
        app.shutdown()


@pytest.mark.parametrize("slots, tasks", [(4, 4), (16, 16), (17, 0),
                                          (256, 0)])
def test_only_a_narrow_graphql_batch_fans_out_into_pool_tasks(
        tmp_path, slots, tasks):
    import urllib.request

    from weaviate_tpu.server import RestServer

    app, idx, vecs = _app(tmp_path)
    mapped = []
    pool_map = app.serving_pool.map

    def spy(fn, items):
        items = list(items)
        mapped.append(len(items))
        return pool_map(fn, items)

    app.serving_pool.map = spy
    srv = RestServer(app, host="127.0.0.1", port=0)
    srv.start()
    try:
        q = "{ Get { Co(nearVector: {vector: %s}, limit: 2) " \
            "{ _additional { id } } } }"
        body = [{"query": q % (vecs[i % N] + np.float32(0.5)).tolist()}
                for i in range(slots)]
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/graphql/batch",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert len(out) == slots
        assert all(len(o["data"]["Get"]["Co"]) == 2 for o in out)
        assert sum(mapped) == tasks
    finally:
        srv.stop()
        app.shutdown()


# -- 5. the programs a lane can take are there before its riders are ----------


def _scan_cache():
    """Compiled shapes of the two full-store programs (the CPU's jits): the
    plan may hand a width to either."""
    from weaviate_tpu.ops import gmin_scan

    return (tpu._search_full_fused._plain._cache_size()
            + gmin_scan.search_gmin_fused._cache_size())


def test_the_first_request_of_a_depth_brings_the_lane_programs(tmp_path):
    """Bucket 1, 4 and 16 of the program a narrow dispatch takes are in the
    jit cache when the first request of a depth after a restore has been
    answered: no later lane meets a first compile with its riders waiting."""
    vecs = np.random.default_rng(9).integers(
        -8, 8, (300, 24)).astype(np.float32)      # a width no test shares
    app, idx, _ = _app(tmp_path, cls="Warm", vecs=vecs)
    app.shutdown()
    before = _scan_cache()
    app, idx, _ = _app(tmp_path, cls="Warm", vecs=vecs)
    try:
        app.db.post_startup()
        restored = _scan_cache()
        assert restored == before      # a restart compiles none of them
        shard = idx.single_local_shard()
        ask = lambda i, k: app.traverser.get_class(GetParams(  # noqa: E731
            class_name="Warm", near_vector={"vector": vecs[i].tolist()},
            limit=k))
        assert len(ask(5, 9)) == 9
        assert _scan_cache() - restored == 3
        # the next requests, alone and in company, compile nothing
        widths = []
        for b in (1, 3, 11):
            h = shard.vector_index.search_by_vectors_async(vecs[:b], 9)
            h()
            widths.append(h.plan.batch_padded)
        assert widths == [1, 4, 16] and len(ask(6, 9)) == 9
        assert _scan_cache() - restored == 3
        # another depth: its first request brings its own three
        assert len(ask(5, 7)) == 7 and _scan_cache() - restored == 6
    finally:
        app.shutdown()


def test_warming_is_once_a_depth_and_width_and_survives_a_failure():
    class _Shard:
        class _Index:
            metric, dim, width = "dot", 4, 256

            def __init__(self):
                self.warmed: list = []

            def lane_width(self, k, cap):
                return self.width

            def search_by_vectors_async(self, q, k):
                self.warmed.append((k, int(q.shape[0])))
                if k == 3:
                    raise RuntimeError("device")
                return lambda: None

        class_def = None

        def __init__(self):
            self.vector_index = self._Index()

        def object_vector_search_async(self, q, k, include_vector=False,
                                       flt=None):
            return lambda: [[float(v[0])] for v in q]

    co = QueryCoalescer()
    shard = _Shard()
    one = np.ones((1, 4), np.float32)
    try:
        # the first lane's maker warms, on its own thread, and is answered
        # through the queue (it does not hold the slot while it compiles)
        assert co.submit(shard, one, 10, wait_now=True)() == [[1.0]]
        assert co.submit(shard, one, 10, wait_now=True)() == [[1.0]]
        assert shard.vector_index.warmed == [(10, 4), (10, 16)]
        # a failure is left to the lane that meets it, and not tried again
        assert co.submit(shard, one, 3)() == [[1.0]]
        assert co.submit(shard, one, 3)() == [[1.0]]
        assert shard.vector_index.warmed[2:] == [(3, 4)]
        # a layout trained later closes the lanes sooner and is another
        # program: warmed anew, up to the new width
        shard.vector_index.width = 4
        assert co.submit(shard, one, 10)() == [[1.0]]
        assert shard.vector_index.warmed[3:] == [(10, 4)]
        # a shard that goes takes its entry with it (once the threads
        # that served its last lanes have served another's)
        other = _Shard()
        assert set(co._warmed) == {shard}
        del shard
        for _ in range(4):
            assert co.submit(other, one, 10)() == [[1.0]]
        import gc
        gc.collect()
        assert set(co._warmed) == {other}
    finally:
        co.shutdown()


# -- 6. the accounts at width above one ----------------------------------------


@pytest.mark.parametrize("b, b_padded", [(1, 1), (3, 4), (4, 4), (9, 16),
                                         (16, 16)])
def test_a_probed_plans_rows_are_what_its_program_reads(b, b_padded):
    nlist, cap_p, top_p = 4096, 352, 64
    view = _view(nlist * cap_p, nlist, cap_p, top_p)
    p = plan_search(view, b, b_padded, 10)
    assert p.ivf == (top_p, 0)
    assert p.rows == p.extra["ivf_rows_read"] \
        == b_padded * top_p * cap_p + nlist
    shape = p.shape(0.0)
    # bytes: every padded query's tiles; FLOPs: a query against its own
    assert shape.bytes() == p.rows * 768 * 4
    assert shape.flops() == 2 * b * (top_p * cap_p + nlist) * 768
    assert shape.extra["probed_fraction"] == round(
        (top_p * cap_p + nlist) / (nlist * cap_p), 4)
    # the cell's roofline at 16 riders stays under 100%: 1.11 GB in the
    # 4.22 ms the program took alone (PERF.md section 6, PR 43) is 32%
    if b_padded == 16:
        assert shape.bytes() / 819e9 / 4.2231e-3 < 1.0


def test_debug_perf_carries_the_queues_counters(tmp_path):
    app, idx, vecs = _app(tmp_path, tweak=_traced)
    try:
        window = perf.get_window()
        ask = lambda i: app.traverser.get_class(GetParams(  # noqa: E731
            class_name="Co", near_vector={"vector": vecs[i].tolist()},
            limit=K))
        # the first request of a depth brings the wider programs (two
        # dispatches of zeros the index counts as any other): not under test
        ask(9)
        window.clear()
        for i in range(3):
            ask(i)
        app.explorer._coalesce_submit(idx, vecs[:40], K, None, False)
        block = window.summary()["coalescer"]
        assert block == {"lanes": 3, "riders": 3, "rows": 3,
                         "riders_per_lane": 1.0,
                         "bypass": {"oversize": 1}, "shed": {}}
        # beside it, what `queries_per_dispatch` reads
        s = window.summary()
        assert s["rows"] / s["dispatches"] == 1.0
        # a rider's wait is an interval on ITS thread, a lane's scatter a
        # phase of the ledger
        assert s["phases"]["queue_wait"]["samples"] == 3
        assert s["phases"]["scatter"]["samples"] == 3
    finally:
        app.shutdown()
