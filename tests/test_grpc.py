"""gRPC Search/BatchSearch service tests.

Reference surface: adapters/handlers/grpc/server.go + grpc/weaviate.proto.
"""

import json
import uuid as uuidlib

import grpc
import numpy as np
import pytest

from weaviate_tpu.grpcapi import weaviate_pb2 as pb
from weaviate_tpu.server import App
from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient
from weaviate_tpu.server.reply_native import varint


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    app = App(data_path=str(tmp_path_factory.mktemp("data")))
    app.schema.add_class({
        "class": "Doc",
        "properties": [
            {"name": "body", "dataType": ["text"]},
            {"name": "rank", "dataType": ["int"]},
        ],
        "vectorIndexConfig": {"distance": "l2-squared"},
    })
    rng = np.random.default_rng(11)
    vecs = rng.standard_normal((30, 16)).astype(np.float32)
    app.batch.add_objects([{
        "class": "Doc",
        "id": str(uuidlib.UUID(int=i + 1)),
        "properties": {"body": f"common term{i} text", "rank": i},
        "vector": vecs[i].tolist(),
    } for i in range(30)])
    srv = GrpcServer(app, port=0)
    srv.start()
    client = SearchClient(f"127.0.0.1:{srv.port}")
    yield app, srv, client, vecs
    client.close()
    srv.stop()
    app.shutdown()


def test_near_vector_search(setup):
    app, srv, client, vecs = setup
    req = pb.SearchRequest(
        class_name="Doc", limit=3,
        near_vector=pb.NearVectorParams(vector=vecs[5].tolist()),
        additional_properties=["distance", "vector"],
    )
    reply = client.search(req)
    assert len(reply.results) == 3
    top = reply.results[0]
    assert top.id == str(uuidlib.UUID(int=6))
    assert top.distance < 1e-3
    assert len(top.vector) == 16
    props = json.loads(top.properties_json)
    assert props["rank"] == 5


def test_property_selection(setup):
    _, _, client, vecs = setup
    req = pb.SearchRequest(
        class_name="Doc", limit=1, properties=["rank"],
        near_vector=pb.NearVectorParams(vector=vecs[0].tolist()))
    props = json.loads(client.search(req).results[0].properties_json)
    assert set(props) == {"rank"}


def test_bm25_and_filter(setup):
    _, _, client, _ = setup
    req = pb.SearchRequest(
        class_name="Doc", limit=5,
        bm25=pb.BM25Params(query="term7"),
    )
    reply = client.search(req)
    assert reply.results and json.loads(reply.results[0].properties_json)["rank"] == 7

    req = pb.SearchRequest(
        class_name="Doc", limit=30,
        where_json=json.dumps(
            {"operator": "GreaterThanEqual", "path": ["rank"], "valueInt": 25}),
    )
    reply = client.search(req)
    ranks = {json.loads(r.properties_json)["rank"] for r in reply.results}
    assert ranks == {25, 26, 27, 28, 29}


def test_unknown_class_aborts(setup):
    _, _, client, vecs = setup
    req = pb.SearchRequest(class_name="Nope", limit=1,
                           near_vector=pb.NearVectorParams(vector=vecs[0].tolist()))
    with pytest.raises(grpc.RpcError) as e:
        client.search(req)
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_batch_search_one_dispatch(setup):
    _, _, client, vecs = setup
    breq = pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="Doc", limit=2,
                         near_vector=pb.NearVectorParams(vector=vecs[i].tolist()))
        for i in range(8)
    ])
    reply = client.batch_search(breq)
    assert len(reply.replies) == 8
    for i, one in enumerate(reply.replies):
        assert one.results[0].id == str(uuidlib.UUID(int=i + 1))


def test_native_reply_marshaller_equivalence(setup):
    """The native wire builder (native/reply.cpp) must produce bytes that
    parse to EXACTLY what the upb marshaller produces, across unicode
    props, empty props, missing distance, and nested JSON."""
    from weaviate_tpu.db.shard import SearchResult
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import reply_native
    from weaviate_tpu.server.grpc_server import fast_reply_bytes, result_to_proto

    assert reply_native.available(), "native reply marshaller must build"
    cases = [
        {"body": "héllo wörld é中文", "rank": 1, "tags": ["a", "b"]},
        {},
        {"nested": {"x": [1.5, None, True], "y": "z"}},
    ]
    results = []
    for i, props in enumerate(cases):
        raw = StorObj(class_name="Doc", uuid=str(uuidlib.UUID(int=900 + i)),
                      properties=props, vector=np.arange(4, dtype=np.float32),
                      doc_id=900 + i).to_binary()
        obj = StorObj.from_binary(raw, include_vector=False)
        results.append(SearchResult(
            obj=obj, distance=0.25 * i if i != 1 else None, shard="s"))
    req = pb.SearchRequest(class_name="Doc", limit=3)
    fast = fast_reply_bytes(results, req, took=0.125)
    assert fast is not None, "fast path must engage for pristine objects"
    got = pb.SearchReply.FromString(fast)
    want = pb.SearchReply(took_seconds=0.125)
    want.results.extend(result_to_proto(r, req) for r in results)
    assert got == want

    # whole-batch builder: two replies (2 + 1 results) parse identically
    raws = [r.obj.raw_if_pristine() for r in results]
    batch = reply_native.build_batch_reply(
        raws, [r.distance for r in results], [None] * 3, [2, 1], 0.125)
    got_b = pb.BatchSearchReply.FromString(batch)
    want_b = pb.BatchSearchReply()
    for rows in (results[:2], results[2:]):
        one = pb.SearchReply(took_seconds=0.125)
        one.results.extend(result_to_proto(r, req) for r in rows)
        want_b.replies.append(one)
    assert got_b == want_b

    # property filtering / vectors / mutated objects refuse the fast path
    assert fast_reply_bytes(
        results, pb.SearchRequest(properties=["rank"]), 0.0) is None
    assert fast_reply_bytes(
        results, pb.SearchRequest(additional_properties=["vector"]), 0.0) is None
    results[0].obj.properties["body"] = "mutated"
    assert fast_reply_bytes(results, req, 0.0) is None


def test_batch_search_uses_native_path(setup):
    """BatchSearch over the real wire must serve nearVector batches through
    the native marshaller (not silently fall back)."""
    from weaviate_tpu.server import grpc_server as gs

    _, _, client, vecs = setup
    calls = []
    orig_one = gs.reply_native.build_search_reply
    orig_batch = gs.reply_native.build_batch_reply

    def spy_one(*a, **k):
        out = orig_one(*a, **k)
        calls.append(out is not None)
        return out

    def spy_batch(*a, **k):
        out = orig_batch(*a, **k)
        calls.append(out is not None)
        return out

    gs.reply_native.build_search_reply = spy_one
    gs.reply_native.build_batch_reply = spy_batch
    try:
        breq = pb.BatchSearchRequest(requests=[
            pb.SearchRequest(class_name="Doc", limit=2,
                             near_vector=pb.NearVectorParams(vector=vecs[i].tolist()))
            for i in range(4)
        ])
        reply = client.batch_search(breq)
    finally:
        gs.reply_native.build_search_reply = orig_one
        gs.reply_native.build_batch_reply = orig_batch
    assert len(reply.replies) == 4 and calls and all(calls)
    for i, one in enumerate(reply.replies):
        assert one.results[0].id == str(uuidlib.UUID(int=i + 1))
        assert json.loads(one.results[0].properties_json)["rank"] == i


def test_raw_batch_lane_equivalence_and_engagement(tmp_path):
    """The zero-object raw lane (device search -> packed native point-gets
    -> packed native reply) must ENGAGE once memtables are flushed, and its
    replies must be message-equal to the general path's — including when a
    winner was deleted between import and serving (dropped by both)."""
    from weaviate_tpu.server.grpc_server import SearchServicer

    app = App(data_path=str(tmp_path / "raw"))
    app.schema.add_class({
        "class": "Raw",
        "properties": [{"name": "rank", "dataType": ["int"]}],
        "vectorIndexConfig": {"distance": "l2-squared"},
    })
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((300, 16)).astype(np.float32)
    app.batch.add_objects([{
        "class": "Raw", "id": str(uuidlib.UUID(int=i + 1)),
        "properties": {"rank": i}, "vector": vecs[i].tolist(),
    } for i in range(300)])
    idx = app.db.get_index("Raw")
    shard = next(iter(idx.shards.values()))
    sv = SearchServicer(app)
    breq = pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="Raw", limit=3,
                         near_vector=pb.NearVectorParams(vector=vecs[i].tolist()))
        for i in range(16)
    ])

    class Ctx:
        def abort(self, *a):
            raise AssertionError(a)

    # memtable-resident: raw lane must decline (exactness), general path serves
    assert sv._raw_batch_lane(breq, 0.0) is None
    got = sv.BatchSearch(breq, Ctx())
    general_before = pb.BatchSearchReply.FromString(
        got if isinstance(got, (bytes, bytearray)) else got.SerializeToString())

    # flush memtables -> segments: the raw lane must now engage
    for b in (shard.objects, shard.docid_lookup):
        b.flush_memtable()
    raw_bytes = sv._raw_batch_lane(breq, 0.0)
    assert raw_bytes is not None, "raw lane did not engage on flushed segments"
    raw = pb.BatchSearchReply.FromString(raw_bytes)
    assert len(raw.replies) == 16
    for i, one in enumerate(raw.replies):
        want = general_before.replies[i]
        assert len(one.results) == len(want.results) == 3
        for a, b_ in zip(one.results, want.results):
            assert a.id == b_.id
            assert abs(a.distance - b_.distance) < 1e-5
            assert json.loads(a.properties_json) == json.loads(b_.properties_json)
            assert a.creation_time_unix == b_.creation_time_unix

    # ineligible requests (properties filter) must decline
    breq2 = pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="Raw", limit=3, properties=["rank"],
                         near_vector=pb.NearVectorParams(vector=vecs[0].tolist()))])
    assert sv._raw_batch_lane(breq2, 0.0) is None
    app.shutdown()


def test_raw_lane_concurrent_searches_and_writes(tmp_path):
    """Production concurrency shape: batch searches hammer the raw lane
    from multiple threads while a writer keeps mutating the class. Every
    reply must be well-formed with correct distances for its own query —
    the lane may bounce between engaged (flushed) and declined (memtable
    busy), but never corrupt a result."""
    import threading

    from weaviate_tpu.server.grpc_server import SearchServicer

    app = App(data_path=str(tmp_path / "conc"))
    app.schema.add_class({
        "class": "C",
        "properties": [{"name": "rank", "dataType": ["int"]}],
        "vectorIndexConfig": {"distance": "l2-squared"},
    })
    rng = np.random.default_rng(9)
    vecs = rng.standard_normal((400, 16)).astype(np.float32)
    app.batch.add_objects([{
        "class": "C", "id": str(uuidlib.UUID(int=i + 1)),
        "properties": {"rank": i}, "vector": vecs[i].tolist(),
    } for i in range(400)])
    idx = app.db.get_index("C")
    shard = next(iter(idx.shards.values()))
    for b in (shard.objects, shard.docid_lookup):
        b.flush_memtable()
    sv = SearchServicer(app)

    class Ctx:
        def abort(self, *a):
            raise AssertionError(a)

    breq = pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="C", limit=3,
                         near_vector=pb.NearVectorParams(vector=vecs[i].tolist()))
        for i in range(16)
    ])
    errors: list = []
    stop = threading.Event()

    def searcher():
        try:
            _searcher()
        except Exception as e:  # noqa: BLE001 — a dead thread must fail the test
            errors.append(("searcher-raised", repr(e)))

    def _searcher():
        while not stop.is_set():
            out = sv.BatchSearch(breq, Ctx())
            rep = pb.BatchSearchReply.FromString(
                out if isinstance(out, (bytes, bytearray))
                else out.SerializeToString())
            if len(rep.replies) != 16:
                errors.append(("replies", len(rep.replies)))
                return
            for i, one in enumerate(rep.replies):
                if one.error_message or not one.results:
                    errors.append((i, one.error_message))
                    return
                # query i is doc i's own vector: its top hit is itself with
                # ~zero distance (docs 0..15 are never touched by the writer)
                if one.results[0].id != str(uuidlib.UUID(int=i + 1)) or \
                        one.results[0].distance > 1e-3:
                    errors.append((i, one.results[0].id,
                                   one.results[0].distance))
                    return

    def writer():
        try:
            _writer()
        except Exception as e:  # noqa: BLE001
            errors.append(("writer-raised", repr(e)))

    def _writer():
        j = 1000
        while not stop.is_set():
            app.batch.add_objects([{
                "class": "C", "id": str(uuidlib.UUID(int=j + 1)),
                "properties": {"rank": j},
                "vector": (rng.standard_normal(16) * 10 + 50).astype(
                    np.float32).tolist(),  # far away: never a top hit
            }])
            j += 1
            if j % 7 == 0:  # re-flush so the raw lane re-engages
                for b in (shard.objects, shard.docid_lookup):
                    b.flush_memtable()

    threads = [threading.Thread(target=searcher) for _ in range(3)]
    wt = threading.Thread(target=writer)
    for t in threads:
        t.start()
    wt.start()
    import time as _t

    _t.sleep(3.0)
    stop.set()
    for t in threads + [wt]:
        t.join()
    assert not errors, errors[:3]
    app.shutdown()


def test_batch_search_per_slot_errors(setup):
    _, _, client, vecs = setup
    breq = pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="Doc", limit=2,
                         near_vector=pb.NearVectorParams(vector=vecs[0].tolist())),
        pb.SearchRequest(class_name="Doc", limit=2, where_json="{not json"),
        pb.SearchRequest(class_name="Ghost", limit=2,
                         near_vector=pb.NearVectorParams(vector=vecs[0].tolist())),
    ])
    reply = client.batch_search(breq)
    assert len(reply.replies) == 3
    assert reply.replies[0].results and not reply.replies[0].error_message
    assert reply.replies[1].error_message  # malformed where_json
    assert reply.replies[2].error_message  # unknown class
    assert not reply.replies[1].results and not reply.replies[2].results


# ---------------------------------------------------------------------------
# The Entry layer's decode: a query vector leaves the request as its packed
# float32 bytes, never as one Python float an element.
# ---------------------------------------------------------------------------

def _len_field(number, payload):
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def _awkward_floats(dim, seed):
    """`dim` float32 bit patterns: quiet NaNs with payloads, both
    infinities, both zeros, denormals, the largest and smallest normals,
    then random bits (signalling NaNs quieted: the old loop took every
    element through a Python float, which quiets them)."""
    special = [0x7FC00001, 0xFFC12345, 0x7FFFFFFF, 0x7F800000, 0xFF800000,
               0x80000000, 0x00000000, 0x00000001, 0x807FFFFF, 0x7F7FFFFF,
               0x00800000, 0x3F800000]
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2**32, dim, dtype=np.uint64).astype(np.uint32)
    nan = (bits & 0x7F800000) == 0x7F800000
    bits[nan] |= 0x00400000
    bits[:min(dim, len(special))] = special[:dim]
    rng.shuffle(bits)
    return bits


def _vector_wire(bits, encoding, number=1):
    """Wire bytes of a `repeated float` field holding `bits`, as a proto3
    parser has to accept them."""
    raw = bits.astype("<u4").tobytes()
    if encoding == "packed":
        return _len_field(number, raw)
    if encoding == "unpacked":  # one fixed32 an element
        tag = varint(number << 3 | 5)
        return b"".join(tag + raw[i:i + 4] for i in range(0, len(raw), 4))
    assert encoding == "split"  # two packed runs (the first may be empty)
    cut = (len(bits) // 3) * 4
    return _len_field(number, raw[:cut]) + _len_field(number, raw[cut:])


def _old_decode(vector, dim):
    return np.fromiter(vector, np.float32, dim)


class _StubShard:
    def raw_plane_ready(self):
        return True


def _stub_servicer():
    """A SearchServicer whose app resolves every class to one ready shard:
    `_raw_batch_decode` runs for real, nothing behind it does."""
    from types import SimpleNamespace as NS

    from weaviate_tpu.server.grpc_server import SearchServicer

    shard = _StubShard()
    app = NS(
        traverser=NS(explorer=NS(query_limit=10, max_results=10000)),
        schema=NS(resolve_class_name=lambda c: c or None),
        db=NS(get_index=lambda c: NS(single_local_shard=lambda: shard)))
    return SearchServicer(app), shard


@pytest.mark.parametrize("encoding", ["packed", "unpacked", "split"])
@pytest.mark.parametrize("dim", [1, 16, 100, 128, 768])
def test_vector_decode_is_bit_exact(dim, encoding):
    """The helper and the raw lane's batch decode give the wire's own bit
    patterns, equal to what the per-element loop gave, whichever encoding
    of the repeated field the client chose."""
    from weaviate_tpu.server.grpc_server import _vector_f32, params_from_proto

    slots = 5
    bits = np.stack([_awkward_floats(dim, 1000 * dim + s)
                     for s in range(slots)])
    # one vector: NearVectorParams (field 1) and HybridParams (field 2,
    # behind its query string)
    nv = pb.NearVectorParams.FromString(_vector_wire(bits[0], encoding))
    assert len(nv.vector) == dim
    got = _vector_f32(nv)
    assert got.dtype == np.float32 and got.shape == (dim,)
    assert (got.view(np.uint32) == bits[0]).all()
    assert (got.view(np.uint32)
            == _old_decode(nv.vector, dim).view(np.uint32)).all()
    hy = pb.HybridParams.FromString(
        _vector_wire(bits[1], encoding, number=2)
        + _len_field(1, "größe".encode()) + b"\x1d" + b"\x00\x00\x00\x3f")
    assert hy.query == "größe" and hy.alpha == 0.5
    assert (_vector_f32(hy).view(np.uint32) == bits[1]).all()
    p = params_from_proto(pb.SearchRequest(class_name="C", hybrid=hy))
    assert (p.hybrid["vector"].view(np.uint32) == bits[1]).all()
    assert p.hybrid["query"] == "größe" and p.hybrid["alpha"] == 0.5

    # the batch: every slot in that encoding, fields in reverse order
    wire = b"".join(
        _len_field(1, _len_field(6, _vector_wire(bits[s], encoding))
                   + b"\x10\x07" + _len_field(1, b"Cls"))
        for s in range(slots))
    breq = pb.BatchSearchRequest.FromString(wire)
    sv, shard = _stub_servicer()
    got_shard, q, k = sv._raw_batch_decode(breq)
    assert got_shard is shard and k == 7
    assert q.dtype == np.float32 and q.shape == (slots, dim)
    assert q.flags.c_contiguous and q.flags.aligned
    assert (q.view(np.uint32) == bits).all()
    old = np.stack([_old_decode(r.near_vector.vector, dim)
                    for r in breq.requests])
    assert (q.view(np.uint32) == old.view(np.uint32)).all()


@pytest.mark.parametrize("case", [
    "certainty", "distance", "both", "reordered", "unknown_field", "empty",
    "hybrid_vector_only", "hybrid_query_only"])
def test_params_from_proto_vector_beside_other_fields(case):
    """`certainty` / `distance` follow the vector in the canonical bytes
    and an unknown field follows those: the helper finds the payload by its
    tag and length, not by counting from the end."""
    import struct

    from weaviate_tpu.server.grpc_server import _vector_f32, params_from_proto

    bits = _awkward_floats(24, 7)
    vec = _vector_wire(bits, "packed")
    cert = b"\x11" + struct.pack("<d", 0.75)
    dist = b"\x19" + struct.pack("<d", 0.25)
    if case in ("hybrid_vector_only", "hybrid_query_only"):
        hy = pb.HybridParams.FromString(
            _vector_wire(bits, "split", number=2)
            if case == "hybrid_vector_only" else _len_field(1, b"words"))
        p = params_from_proto(pb.SearchRequest(class_name="C", hybrid=hy))
        if case == "hybrid_vector_only":
            assert (p.hybrid["vector"].view(np.uint32) == bits).all()
            assert p.hybrid["query"] == ""
        else:
            assert "vector" not in p.hybrid
            assert _vector_f32(hy).shape == (0,)
        return
    wire = {
        "certainty": vec + cert,
        "distance": vec + dist,
        "both": vec + cert + dist,
        # distance first, two fixed32 elements, then a packed run
        "reordered": dist + _vector_wire(bits[:2], "unpacked")
        + _vector_wire(bits[2:], "packed"),
        "unknown_field": _len_field(15, b"hi") + vec + b"\x28\x07" + dist,
        "empty": dist,
    }[case]
    nv = pb.NearVectorParams.FromString(wire)
    p = params_from_proto(pb.SearchRequest(class_name="C", near_vector=nv))
    if case == "empty":
        assert p.near_vector is None
        assert _vector_f32(nv).shape == (0,)
        assert _vector_f32(pb.NearVectorParams()).shape == (0,)
        return
    assert (p.near_vector["vector"].view(np.uint32) == bits).all()
    assert p.near_vector.get("certainty") == (
        0.75 if case in ("certainty", "both") else None)
    assert p.near_vector.get("distance") == (
        None if case == "certainty" else 0.25)


_PLAIN = dict(class_name="Cls", limit=3)


def _plain_slot(dim=16, seed=0, **over):
    vec = np.random.default_rng(seed).standard_normal(dim).astype(np.float32)
    kw = dict(_PLAIN, near_vector=pb.NearVectorParams(vector=vec.tolist()))
    kw.update(over)
    return pb.SearchRequest(**kw)


def _odd_slot(what):
    """A slot the raw lane has to refuse, or its wire bytes."""
    nv = lambda **kw: pb.NearVectorParams(  # noqa: E731
        vector=[0.5] * kw.pop("dim", 16), **kw)
    if what == "unknown_field_in_slot":
        return _plain_slot().SerializeToString() + _len_field(15, b"new")
    if what == "unknown_field_in_near_vector":
        return pb.SearchRequest(**_PLAIN).SerializeToString() + _len_field(
            6, nv().SerializeToString() + b"\x28\x01")
    return {
        "narrower": lambda: _plain_slot(near_vector=nv(dim=15)),
        "wider": lambda: _plain_slot(near_vector=nv(dim=17)),
        "other_class": lambda: _plain_slot(class_name="Clt"),
        "no_class": lambda: _plain_slot(class_name=""),
        "other_limit": lambda: _plain_slot(limit=4),
        "no_limit": lambda: _plain_slot(limit=0),
        "offset": lambda: _plain_slot(offset=1),
        "properties": lambda: _plain_slot(properties=["rank"]),
        "additional": lambda: _plain_slot(additional_properties=["vector"]),
        "where": lambda: _plain_slot(where_json="{}"),
        "consistency": lambda: _plain_slot(consistency_level="ONE"),
        "no_near_vector": lambda: pb.SearchRequest(**_PLAIN),
        "empty_vector": lambda: _plain_slot(
            near_vector=pb.NearVectorParams(distance=0.5)),
        "certainty": lambda: _plain_slot(near_vector=nv(certainty=0.0)),
        "distance": lambda: _plain_slot(near_vector=nv(distance=0.5)),
        "near_object": lambda: _plain_slot(
            near_object=pb.NearObjectParams(id="x")),
        "bm25": lambda: _plain_slot(bm25=pb.BM25Params(query="x")),
        "hybrid": lambda: _plain_slot(hybrid=pb.HybridParams(query="x")),
    }[what]()


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("what", [
    "narrower", "wider", "other_class", "no_class", "other_limit",
    "no_limit", "offset", "properties", "additional", "where", "consistency",
    "no_near_vector", "empty_vector", "certainty", "distance", "near_object",
    "bm25", "hybrid", "unknown_field_in_slot",
    "unknown_field_in_near_vector"])
def test_raw_lane_declines_a_batch_with_one_odd_slot(what, where):
    """The raw lane serves a batch only if EVERY slot is class, limit and a
    vector of the batch's width and nothing else; one odd slot anywhere
    and the general path gets the whole batch."""
    sv, _ = _stub_servicer()
    slots = [_plain_slot(seed=s) for s in range(6)]
    plain = pb.BatchSearchRequest(requests=slots)
    assert sv._raw_batch_decode(plain)[1].shape == (6, 16)
    odd = _odd_slot(what)
    at = {"first": 0, "middle": 3, "last": 5}[where]
    wire = b"".join(
        _len_field(1, s if isinstance(s, bytes) else s.SerializeToString())
        for s in slots[:at] + [odd] + slots[at + 1:])
    assert sv._raw_batch_decode(pb.BatchSearchRequest.FromString(wire)) is None


def test_raw_lane_declines_widths_that_cancel_and_top_level_extras():
    """Two slots whose widths differ by +1 and -1 leave the request's
    length as it was; a field of BatchSearchRequest this build does not
    know follows the slots. Both decline."""
    sv, _ = _stub_servicer()
    nv = lambda d: pb.NearVectorParams(vector=[0.5] * d)  # noqa: E731
    breq = pb.BatchSearchRequest(requests=[
        _plain_slot(), _plain_slot(near_vector=nv(15)),
        _plain_slot(near_vector=nv(17)), _plain_slot()])
    assert breq.ByteSize() == pb.BatchSearchRequest(
        requests=[_plain_slot()] * 4).ByteSize()
    assert sv._raw_batch_decode(breq) is None
    plain = pb.BatchSearchRequest(requests=[_plain_slot()] * 4)
    assert sv._raw_batch_decode(plain) is not None
    extra = pb.BatchSearchRequest.FromString(
        plain.SerializeToString() + b"\x10\x01")
    assert sv._raw_batch_decode(extra) is None
    assert sv._raw_batch_decode(pb.BatchSearchRequest()) is None


def _c_calls(fn):
    """How many C functions `fn()` calls, by the interpreter's own count:
    no clock in it."""
    import sys

    n = 0

    def prof(frame, event, arg):
        nonlocal n
        if event == "c_call":
            n += 1

    sys.setprofile(prof)
    try:
        out = fn()
    finally:
        sys.setprofile(None)
    return n, out


def test_decode_makes_no_call_per_element():
    """A 256 x 768 BatchSearchRequest is decoded in fewer than 16 C calls a
    slot (the per-element loop made 768 and more a slot), and in fact in
    fewer than one: the raw lane does no Python work a slot. So a later
    edit cannot bring either loop back unnoticed."""
    from weaviate_tpu.server.grpc_server import params_from_proto

    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((256, 768)).astype(np.float32)
    breq = pb.BatchSearchRequest.FromString(pb.BatchSearchRequest(requests=[
        pb.SearchRequest(class_name="Cls", limit=10,
                         near_vector=pb.NearVectorParams(vector=v.tolist()))
        for v in vecs]).SerializeToString())
    sv, _ = _stub_servicer()
    calls, (_, q, _) = _c_calls(lambda: sv._raw_batch_decode(breq))
    assert (q == vecs).all()
    assert calls < 16 * 256
    assert calls < 256, calls
    # the general path's decode, a slot at a time
    calls, params = _c_calls(
        lambda: [params_from_proto(r) for r in breq.requests])
    assert (np.stack([p.near_vector["vector"] for p in params]) == vecs).all()
    assert calls < 16 * 256, calls


def test_hybrid_vector_reaches_the_dense_leg(setup):
    """A hybrid query's vector takes the same decode: with alpha 1 it is a
    near-vector search by another name."""
    _, _, client, vecs = setup
    near = client.search(pb.SearchRequest(
        class_name="Doc", limit=3,
        near_vector=pb.NearVectorParams(vector=vecs[4].tolist())))
    hyb = client.search(pb.SearchRequest(
        class_name="Doc", limit=3,
        hybrid=pb.HybridParams(vector=vecs[4].tolist(), alpha=1.0)))
    assert [r.id for r in hyb.results] == [r.id for r in near.results]
    assert hyb.results[0].id == str(uuidlib.UUID(int=5))
