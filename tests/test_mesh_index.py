"""MeshVectorIndex ("hnsw_tpu_mesh") on the virtual 8-device CPU mesh:
brute-force parity, deletes, filters, growth, durability replay, and the
full serving path through DB/ClassIndex/Shard."""

import uuid as uuidlib

import jax
import numpy as np
import pytest

from weaviate_tpu.db import DB
from weaviate_tpu.entities.filters import LocalFilter
from weaviate_tpu.entities.schema import ClassDef, Property
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.entities.vectorindex import (
    ConfigValidationError,
    parse_and_validate_config,
)
from weaviate_tpu.index.mesh import MeshVectorIndex
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16
SENTINEL = np.iinfo(np.uint64).max


def make_index(tmp_path, metric="l2-squared", persist=True, **cfg):
    config = parse_and_validate_config("hnsw_tpu_mesh", {"distance": metric, **cfg})
    return MeshVectorIndex(
        config, str(tmp_path), persist=persist, initial_capacity_per_shard=64
    )


def brute(vecs, ids, q, k, metric="l2-squared"):
    if metric == "l2-squared":
        d = ((vecs - q) ** 2).sum(1)
    elif metric == "cosine":
        vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
        qn = q / np.linalg.norm(q)
        d = 1.0 - vn @ qn
    else:
        d = -(vecs @ q)
    order = np.argsort(d, kind="stable")[:k]
    return ids[order], d[order]


def test_devices():
    assert len(jax.devices()) >= 8


def test_bruteforce_parity(tmp_path, rng):
    idx = make_index(tmp_path)
    n = 700
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    ids = np.arange(10, 10 + n)
    idx.add_batch(ids, vecs)
    qs = rng.standard_normal((5, DIM)).astype(np.float32)
    got_ids, got_d = idx.search_by_vectors(qs, 10)
    assert got_ids.shape == (5, 10)
    for bi in range(5):
        want_ids, want_d = brute(vecs, ids, qs[bi], 10)
        assert set(got_ids[bi].tolist()) == set(want_ids.tolist())
        np.testing.assert_allclose(np.sort(got_d[bi]), np.sort(want_d), rtol=1e-4)
    idx.shutdown()


def test_cosine_metric(tmp_path, rng):
    idx = make_index(tmp_path, metric="cosine")
    vecs = rng.standard_normal((200, DIM)).astype(np.float32)
    ids = np.arange(200)
    idx.add_batch(ids, vecs)
    q = vecs[7]
    got_ids, got_d = idx.search_by_vector(q, 5)
    assert got_ids[0] == 7
    assert got_d[0] < 1e-5
    idx.shutdown()


def test_delete_and_update(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((100, DIM)).astype(np.float32)
    idx.add_batch(np.arange(100), vecs)
    assert len(idx) == 100
    # delete the true nearest neighbor of q; it must vanish from results
    q = vecs[42]
    idx.delete(42)
    assert len(idx) == 99
    assert not idx.contains(42)
    got_ids, _ = idx.search_by_vector(q, 5)
    assert 42 not in got_ids.tolist()
    # re-add with a new vector: old row tombstoned, new one found
    newv = rng.standard_normal(DIM).astype(np.float32)
    idx.add(42, newv)
    got_ids, got_d = idx.search_by_vector(newv, 1)
    assert got_ids[0] == 42 and got_d[0] < 1e-5
    assert len(idx) == 100
    idx.shutdown()


def test_filtered_search_bitmap(tmp_path, rng):
    idx = make_index(tmp_path)
    n = 300
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    ids = np.arange(n)
    idx.add_batch(ids, vecs)
    allowed = np.arange(0, n, 3).astype(np.uint64)  # every 3rd doc
    allow = Bitmap(allowed)
    q = vecs[5]  # 5 is not allowed (5 % 3 != 0)
    got_ids, got_d = idx.search_by_vectors(q[None], 10, allow_list=allow)
    real = got_ids[0][got_ids[0] != SENTINEL]
    assert len(real) == 10
    assert all(int(i) % 3 == 0 for i in real)
    want_ids, _ = brute(vecs[::3], ids[::3], q, 10)
    assert set(int(i) for i in real) == set(want_ids.tolist())
    idx.shutdown()


def test_growth_beyond_initial_capacity(tmp_path, rng):
    idx = make_index(tmp_path)  # 64 rows/chip * 8 chips = 512 initial
    n = 2000
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    assert len(idx) == n
    assert idx.n_loc > 64
    q = vecs[1777]
    got_ids, got_d = idx.search_by_vector(q, 3)
    assert got_ids[0] == 1777 and got_d[0] < 1e-5
    idx.shutdown()


def test_durability_replay(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((150, DIM)).astype(np.float32)
    idx.add_batch(np.arange(150), vecs)
    idx.delete(3, 77)
    idx.add(300, vecs[0] * 2.0)
    idx.shutdown()

    idx2 = make_index(tmp_path)
    assert len(idx2) == 149  # 150 - 2 deleted + 1 added
    assert not idx2.contains(3) and not idx2.contains(77)
    assert idx2.contains(300)
    got_ids, got_d = idx2.search_by_vector(vecs[10], 1)
    assert got_ids[0] == 10 and got_d[0] < 1e-5
    idx2.shutdown()


def test_compact_drops_tombstones(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((120, DIM)).astype(np.float32)
    idx.add_batch(np.arange(120), vecs)
    idx.delete(*range(0, 120, 2))
    assert len(idx) == 60
    idx.compact()
    assert len(idx) == 60
    assert int(idx._counts.sum()) == 60  # tombstoned slots physically gone
    got_ids, got_d = idx.search_by_vector(vecs[1], 5)
    assert got_ids[0] == 1 and got_d[0] < 1e-5
    assert all(int(i) % 2 == 1 for i in got_ids.tolist())
    idx.shutdown()


def test_insert_with_full_shards_keeps_live_rows(tmp_path, rng):
    """Regression: a whole-mesh insert step must leave chips with no work
    bit-identical — a full slab's clamped offset would otherwise zero its
    last live row."""
    idx = make_index(tmp_path)  # 64 rows/chip * 8 chips
    n = 8 * 64 - 1  # fill every slab except one row on one chip
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    idx.add(n, rng.standard_normal(DIM).astype(np.float32))  # 7 chips idle
    # every original vector must still be found exactly
    probe = rng.integers(0, n, 32)
    for i in probe:
        got_ids, got_d = idx.search_by_vector(vecs[i], 1)
        assert got_ids[0] == i and got_d[0] < 1e-5, i
    idx.shutdown()


def test_delete_then_grow_keeps_tombstones(tmp_path, rng):
    """Regression: tombstones staged before a growth must land on the
    remapped rows, and the deleted doc must not resurrect through the
    rebuilt id map."""
    idx = make_index(tmp_path)
    n = 512
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    idx.delete(300)  # staged tombstone at old slab layout
    more = rng.standard_normal((4096, DIM)).astype(np.float32)
    idx.add_batch(np.arange(10_000, 14_096), more)  # triggers growth
    assert not idx.contains(300)
    got_ids, _ = idx.search_by_vector(vecs[300], 5)
    assert 300 not in got_ids.tolist()
    # every other original row survived the grow + masked writes
    for i in (0, 1, 299, 301, 511):
        got_ids, got_d = idx.search_by_vector(vecs[i], 1)
        assert got_ids[0] == i and got_d[0] < 1e-5, i
    # compact must not re-add the deleted row either
    idx.compact()
    assert not idx.contains(300)
    got_ids, _ = idx.search_by_vector(vecs[300], 5)
    assert 300 not in got_ids.tolist()
    idx.shutdown()


def test_pq_on_mesh(tmp_path, rng):
    """Mesh PQ (compress.go parity): compress -> recall vs brute force,
    filtered PQ search, post-compress appends encode on write, store
    downcast to bf16."""
    import jax.numpy as jnp

    idx = make_index(tmp_path / "pq")
    vecs = rng.standard_normal((400, DIM)).astype(np.float32)
    idx.add_batch(np.arange(400), vecs)
    idx.flush()
    assert idx.dtype == jnp.float32
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared", "pq": {"enabled": True, "segments": 4}}))
    assert idx.compressed and idx.dtype == jnp.bfloat16

    q = vecs[7] + 0.01
    ids, dists = idx.search_by_vector(q, 5)
    want_ids, _ = brute(vecs, np.arange(400), q, 5)
    assert ids[0] == want_ids[0] == 7
    assert len(set(int(x) for x in ids) & set(int(x) for x in want_ids)) >= 4

    # filtered PQ search
    allow = Bitmap(range(100, 200))
    ids_f, _ = idx.search_by_vectors(vecs[150][None, :] + 0.01, 3, allow_list=allow)
    assert int(ids_f[0][0]) == 150
    assert all(100 <= int(x) < 200 for x in ids_f[0])

    # post-compress append is searchable (encode-on-write)
    nv = rng.standard_normal(DIM).astype(np.float32) * 5.0
    idx.add(9999, nv)
    idx.flush()
    ids2, _ = idx.search_by_vector(nv, 1)
    assert int(ids2[0]) == 9999

    # delete under PQ
    idx.delete(7)
    ids3, _ = idx.search_by_vector(q, 3)
    assert 7 not in [int(x) for x in ids3]


def test_pq_mesh_restart(tmp_path, rng):
    """Codebook persists; codes re-derive on replay (AddPQ replay parity)."""
    idx = make_index(tmp_path / "pqr")
    vecs = rng.standard_normal((300, DIM)).astype(np.float32)
    idx.add_batch(np.arange(300), vecs)
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared", "pq": {"enabled": True, "segments": 4}}))
    idx.flush()
    del idx

    idx2 = make_index(tmp_path / "pqr")
    assert idx2.compressed
    q = vecs[11] + 0.005
    ids, _ = idx2.search_by_vector(q, 3)
    assert int(ids[0]) == 11
    # compact under PQ keeps searchability
    idx2.delete(0, 1, 2)
    idx2.compact()
    ids2, _ = idx2.search_by_vector(q, 3)
    assert int(ids2[0]) == 11 and 0 not in [int(x) for x in ids2]


def test_search_by_vector_distance(tmp_path, rng):
    idx = make_index(tmp_path)
    base = rng.standard_normal(DIM).astype(np.float32)
    vecs = base + 0.01 * np.arange(50)[:, None].astype(np.float32)
    idx.add_batch(np.arange(50), vecs.astype(np.float32))
    ids, dists = idx.search_by_vector_distance(vecs[0], target_distance=0.01, max_limit=100)
    assert len(ids) > 0
    assert (dists <= 0.01).all()
    idx.shutdown()


# -- through the serving path (Shard / ClassIndex / DB) ----------------------


def make_class(name="MeshArticle"):
    return ClassDef(
        name=name,
        properties=[
            Property(name="title", data_type=["text"]),
            Property(name="wordCount", data_type=["int"]),
            Property(name="published", data_type=["boolean"]),
        ],
        vector_index_type="hnsw_tpu_mesh",
    )


def new_obj(i, dim=8, cls="MeshArticle"):
    rng = np.random.default_rng(i)
    return StorObj(
        class_name=cls,
        uuid=str(uuidlib.UUID(int=i + 1)),
        properties={"title": f"hello {i}", "wordCount": i, "published": i % 2 == 0},
        vector=rng.standard_normal(dim).astype(np.float32),
    )


def test_mesh_through_shard(tmp_path):
    cfg = parse_and_validate_config("hnsw_tpu_mesh", {"distance": "l2-squared"})
    db = DB(str(tmp_path / "data"))
    idx = db.add_class(make_class(), cfg)
    objs = [new_obj(i) for i in range(60)]
    idx.put_batch(objs)

    res = idx.object_vector_search(objs[17].vector, k=5)
    assert res[0][0].obj.uuid == objs[17].uuid

    # filtered search goes through the device bitmap path
    flt = LocalFilter.from_dict(
        {"operator": "Equal", "path": ["published"], "valueBoolean": True}
    )
    res = idx.object_vector_search(objs[4].vector, k=10, flt=flt)
    assert len(res[0]) == 10
    assert all(r.obj.properties["published"] is True for r in res[0])

    # delete through the shard: object disappears from vector results
    idx.delete_object(objs[17].uuid)
    res = idx.object_vector_search(objs[17].vector, k=5)
    assert all(r.obj.uuid != objs[17].uuid for r in res[0])
    db.shutdown()


def test_mesh_restart_through_db(tmp_path):
    cfg = parse_and_validate_config("hnsw_tpu_mesh", {"distance": "l2-squared"})
    db1 = DB(str(tmp_path / "data"))
    idx = db1.add_class(make_class(), cfg)
    objs = [new_obj(i) for i in range(40)]
    idx.put_batch(objs)
    idx.delete_object(objs[8].uuid)
    db1.flush()
    db1.shutdown()

    db2 = DB(str(tmp_path / "data"))
    idx2 = db2.add_class(make_class(), cfg)
    assert idx2.object_count() == 39
    res = idx2.object_vector_search(objs[3].vector, k=3)
    assert res[0][0].obj.uuid == objs[3].uuid
    res = idx2.object_vector_search(objs[8].vector, k=5)
    assert all(r.obj.uuid != objs[8].uuid for r in res[0])
    db2.shutdown()


def test_pq_mesh_large_k_and_manhattan_guard(tmp_path, rng):
    """k > r_chunk cap exercises the pool-covers-k clamp; non-matmul
    metrics refuse to compress instead of silently mis-scoring."""
    config = parse_and_validate_config(
        "hnsw_tpu_mesh", {"distance": "l2-squared"})
    idx = MeshVectorIndex(config, str(tmp_path / "pqk"),
                          initial_capacity_per_shard=1024)
    vecs = rng.standard_normal((400, DIM)).astype(np.float32)
    idx.add_batch(np.arange(400), vecs)
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared", "pq": {"enabled": True, "segments": 4}}))
    ids, dists = idx.search_by_vectors(vecs[:2] + 0.001, 300)
    real = ids[0][dists[0] != np.inf]
    assert len(real) >= 300 - 1  # pool covered k

    man = make_index(tmp_path / "man", metric="manhattan")
    mvecs = rng.standard_normal((300, DIM)).astype(np.float32)
    man.add_batch(np.arange(300), mvecs)
    with pytest.raises(ConfigValidationError):
        man.update_user_config(parse_and_validate_config(
            "hnsw_tpu_mesh",
            {"distance": "manhattan", "pq": {"enabled": True, "segments": 4}}))
    # the rejected pq-enable must not stick in config: adds and searches
    # keep working (a sticky pq.enabled would re-raise from _flush_pending)
    assert not man.config.pq.enabled
    man.add_batch(np.arange(300, 320),
                  rng.standard_normal((20, DIM)).astype(np.float32))
    ids, _ = man.search_by_vectors(mvecs[:1], 5)
    assert ids[0][0] == 0


def test_mesh_bulk_replay_matches_prerestart(tmp_path, rng):
    """A large (>256-record runs) mixed log — adds, deletes, re-adds,
    in-run duplicates — restores onto the mesh with the exact pre-restart
    state via the bulk replay path."""
    config = parse_and_validate_config("hnsw_tpu_mesh", {"distance": "l2-squared"})
    idx = MeshVectorIndex(config, str(tmp_path / "br"),
                          initial_capacity_per_shard=1024)
    n = 1500
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    idx.delete(*range(0, 50, 2))
    idx.add_batch(np.arange(10), vecs[500:510])  # re-adds incl. deleted
    dup_vecs = rng.standard_normal((3, DIM)).astype(np.float32)
    idx.add_batch(np.array([7, 7, 7]), dup_vecs)
    idx.flush()
    live_ref = idx.live
    ids_ref, d_ref = idx.search_by_vectors(vecs[100:116], 3)
    idx.flush()
    del idx

    idx2 = MeshVectorIndex(config, str(tmp_path / "br"),
                           initial_capacity_per_shard=1024)
    assert idx2.live == live_ref
    ids2, d2 = idx2.search_by_vectors(vecs[100:116], 3)
    np.testing.assert_allclose(d2, d_ref, atol=1e-4)
    ids7, d7 = idx2.search_by_vector(dup_vecs[2], 1)
    assert ids7[0] == 7 and d7[0] < 1e-5


def test_mesh_gmin_fused_kernel_matches_exact(tmp_path, rng):
    """Slabs big enough for the fused group-min path (n_loc >= 16384):
    results must match exact numpy, the kernel must actually engage, and
    deletes + filters must hold (interpret mode on the CPU mesh)."""
    from weaviate_tpu.storage.bitmap import Bitmap

    config = parse_and_validate_config("hnsw_tpu_mesh", {"distance": "l2-squared"})
    idx = MeshVectorIndex(config, str(tmp_path / "g"),
                          initial_capacity_per_shard=16384)
    n = 3000
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    for doc in range(0, 30, 2):
        idx.delete(doc)
    q = vecs[:16] + 0.001 * rng.standard_normal((16, DIM)).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    # the fused path was eligible AND actually served (validated shape)
    assert not idx._gmin_broken and idx._gmin_validated
    from weaviate_tpu.index.plan import plan_search
    assert plan_search(idx._plan_view(idx._read_snapshot()[0]), 16, 16,
                       5).program == "gmin"
    live = np.array([d for d in range(n) if not (d < 30 and d % 2 == 0)])
    dd = ((q[:, None, :] - vecs[live][None, :, :]) ** 2).sum(-1)
    want = live[np.argsort(dd, axis=1)[:, :5]]
    for i in range(16):
        assert set(int(x) for x in ids[i]) == set(int(x) for x in want[i]), i
    # filtered: allowList restricted to docs < 500
    allow = Bitmap(np.arange(500).astype(np.uint64))
    ids_f, _ = idx.search_by_vectors(q, 5, allow)
    flat = ids_f[ids_f != np.uint64(0xFFFFFFFFFFFFFFFF)]
    assert all(int(x) < 500 for x in flat)


def test_mesh_pq_codes_fused_kernel_matches_legacy(tmp_path, rng):
    """Codes-only tier on the mesh: slabs big enough for the fused
    per-shard ADC kernel (n_loc/G >= 64) must serve through it (separate
    validation domain), with the same winners as the legacy reconstruction
    scan."""
    config = parse_and_validate_config(
        "hnsw_tpu_mesh", {"distance": "l2-squared"})
    idx = MeshVectorIndex(config, str(tmp_path / "pqm"),
                          initial_capacity_per_shard=1024)
    n = 2000
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared",
         "pq": {"enabled": True, "segments": 8, "centroids": 32,
                "rescore": False}}))
    assert idx.compressed
    q = vecs[:16] + 0.001 * rng.standard_normal((16, DIM)).astype(np.float32)
    ids_f, d_f = idx.search_by_vectors(q, 5)
    assert idx._pqg_state._gmin_validated and not idx._pqg_state._gmin_broken
    idx._pqg_state._gmin_broken = True  # force the legacy recon scan
    ids_l, d_l = idx.search_by_vectors(q, 5)
    idx._pqg_state._gmin_broken = False
    for i in range(16):
        assert set(int(x) for x in ids_f[i]) == set(int(x) for x in ids_l[i]), i
        # the legacy scan computes ADC in bf16 matmuls; the fused path
        # rescores its candidates in f32 — same quantizer, small skew
        np.testing.assert_allclose(np.sort(d_f[i]), np.sort(d_l[i]),
                                   rtol=0.08, atol=0.05)
    # deletes hold through the fused path
    idx.delete(0, 2)
    ids_d, _ = idx.search_by_vectors(q[:4], 3)
    flat = ids_d.ravel()
    assert 0 not in [int(x) for x in flat] and 2 not in [int(x) for x in flat]


def test_pq_mesh_compact_keeps_f32_log(tmp_path, rng):
    """compact() under PQ rewrites the log from the f32 host copy, not the
    bf16-downcast device store."""
    idx = make_index(tmp_path / "pqc")
    vecs = rng.standard_normal((300, DIM)).astype(np.float32)
    idx.add_batch(np.arange(300), vecs)
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared", "pq": {"enabled": True, "segments": 4}}))
    idx.delete(0, 1)
    idx.compact()
    idx.flush()
    del idx
    # replayed vectors are bit-exact f32 originals
    from weaviate_tpu.index.tpu import VectorLog
    got = {doc: vec for op, doc, vec in VectorLog.replay(
        str(tmp_path / "pqc" / "vector.log")) if op == "add"}
    np.testing.assert_array_equal(got[42], vecs[42])
    assert 0 not in got and 1 not in got


def _f32_reference(rows, q, metric):
    """The float32 distance of every row, summed in float64 so that the
    reference's own rounding stays under the tolerance it is used at."""
    rows, q = rows.astype(np.float64), q.astype(np.float64)
    if metric == "l2-squared":
        return ((rows - q) ** 2).sum(1)
    if metric == "dot":
        return -(rows @ q)
    return 1.0 - rows @ q


@pytest.mark.parametrize("exact_topk", [False, True],
                         ids=["rescored", "exactTopK"])
@pytest.mark.parametrize("tombstones", [False, True],
                         ids=["all-live", "tombstones"])
@pytest.mark.parametrize("use_allow", [False, True],
                         ids=["unfiltered", "allowList"])
@pytest.mark.parametrize("metric", ["cosine", "l2-squared", "dot"])
def test_exact_tier_answers_are_the_f32_brute_force(
        tmp_path, metric, use_allow, tombstones, exact_topk):
    """The mesh's exact tier on four virtual chips is the one-chip scan step
    on each slab: the ids are exact float32 brute force's over the rows that
    are live and allowed, every returned distance is the float32 distance of
    the row returned, and the dispatch's shape says at which depth the step
    ran (0 under exactTopK: the HIGHEST-precision scan). The allowList
    leaves every chip fewer rows than the depth, so the (+inf, -1) filler of
    a short slab goes through the rescore and the merge."""
    from weaviate_tpu.monitoring import tracing
    from weaviate_tpu.parallel.mesh_search import make_mesh

    rng = np.random.default_rng(36)
    n, dim, k = 600, 32, 5
    config = parse_and_validate_config(
        "hnsw_tpu_mesh", {"distance": metric, "exactTopK": exact_topk})
    idx = MeshVectorIndex(config, str(tmp_path), persist=False,
                          mesh=make_mesh(4), initial_capacity_per_shard=64)
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    ids = np.arange(100, 100 + n)
    idx.add_batch(ids, vecs)
    live = np.ones(n, bool)
    if tombstones:
        dead = np.arange(0, n, 3)
        idx.delete(*(int(i) for i in ids[dead]))
        live[dead] = False
    allow = None
    if use_allow:
        allowed = np.arange(0, n, 7)          # 86 rows: ~21 a chip, under R
        allow = Bitmap(ids[allowed].astype(np.uint64))
        mask = np.zeros(n, bool)
        mask[allowed] = True
        live &= mask
    qs = vecs[rng.integers(0, n, 6)] + 0.05 * rng.standard_normal(
        (6, dim)).astype(np.float32)
    qs = (qs / np.linalg.norm(qs, axis=1, keepdims=True)).astype(np.float32)

    prev = tracing.get_tracer()
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    try:
        handle = idx.search_by_vectors_async(qs, k, allow_list=allow)
        got_ids, got_d = handle()
        shape = handle.shape
    finally:
        tracing.configure(prev)
    assert shape.tier == "exact_scan" and shape.ndev == 4
    assert shape.extra["rescore_r"] == (0 if exact_topk else 32)
    assert shape.describe()["rescore_r"] == shape.extra["rescore_r"]
    # and /debug/perf tallies the window's dispatches by that depth
    from weaviate_tpu.monitoring import perf
    window = perf.PerfWindow()
    shape.t_end = shape.t_start + 1e-3
    window.record_dispatch(shape)
    assert window.summary()["rescore_r"] == {
        str(shape.extra["rescore_r"]): 1}

    assert got_ids.shape == (6, k)
    for bi in range(6):
        ref = _f32_reference(vecs, qs[bi], metric)
        want = np.argsort(np.where(live, ref, np.inf), kind="stable")[:k]
        assert got_ids[bi].tolist() == ids[want].tolist()
        np.testing.assert_allclose(got_d[bi], ref[want], rtol=0, atol=1e-6)
    idx.shutdown()
