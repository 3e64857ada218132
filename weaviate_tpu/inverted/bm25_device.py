"""Device (TPU) BM25 engine: dense impact rows + one top_k per query.

The keyword half of hybrid search, on the same chip as the vector half.
Reference behavior: adapters/repos/db/inverted/bm25_searcher.go:77 (BM25F
over map buckets); this engine produces the same ranking as the host
MaxScore engine (inverted/bm25.py) and falls back to it wherever the
host path is strictly better:

- additional_explanations (per-term breakdown needs posting positions),
- empty/unknown terms only, or a corpus too small to be worth a device
  round trip (DEVICE_MIN_POSTINGS),
- backend init failure (no usable jax device).

Dense rows are cached on device per (property, term) under the shard
write generation — the same invalidation discipline as the host engine's
posting/length caches (bm25.py), including the mid-write guard: the
writer bumps the generation BEFORE mutating, so a row built mid-write is
never pinned under the new generation. allowLists ride along as a dense
bool mask, cached per (filter key, generation) like the vector side's
scatter-packed masks (index/tpu.py).
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from weaviate_tpu.index.interface import AllowList
from weaviate_tpu.inverted.bm25 import BM25Searcher
from weaviate_tpu.monitoring import costmodel
from weaviate_tpu.monitoring.metrics import record_device_fallback

# below this many total postings the host engine wins: one device dispatch
# costs more than scoring a handful of arrays in numpy
DEVICE_MIN_POSTINGS = 0  # tuned by bench; 0 = always device when eligible

# device bytes pinned for dense rows (a row is n_pad * 4 bytes; at 1M docs
# each cached term costs ~4 MB). A batch sweep whose distinct-term working
# set exceeds this THRASHES (each slice's builds evict the previous
# slice's rows, so the next sweep rebuilds everything); on a 16 GB-HBM
# chip 512 MB alongside a 512 MB store is the right trade, and heavy
# keyword fleets can raise it via WEAVIATE_TPU_BM25_ROW_CACHE_MB.
try:
    _ROW_CACHE_MAX_BYTES = int(
        os.environ.get("WEAVIATE_TPU_BM25_ROW_CACHE_MB") or 512
    ) * 1024 * 1024
except ValueError:  # malformed value must not take the server down
    _ROW_CACHE_MAX_BYTES = 512 * 1024 * 1024

# transient device bytes one batched matmul may stack ([U_pad, n_pad] f32);
# batches whose distinct-unit set would exceed this are processed in
# slices (one dispatch + one fetch per slice) — bounds the working set so
# a wide BatchSearch cannot starve concurrent vector queries of HBM
_BATCH_STACK_MAX_BYTES = 256 * 1024 * 1024


class DeviceBM25:
    """Wraps a host BM25Searcher; owns the device row/mask caches."""

    def __init__(self, searcher: BM25Searcher, gen_fn=None):
        self.searcher = searcher
        self._gen_fn = gen_fn if gen_fn is not None else searcher._gen_fn
        # (prop, term) -> (gen, n_pad, device row [n_pad] f32)
        self._rows: OrderedDict[tuple, tuple] = OrderedDict()
        self._row_bytes = 0
        # id(bitmap) -> (gen, n_pad, device mask, pinned bitmap)
        self._masks: dict[int, tuple] = {}
        self._npad_hwm: Optional[tuple] = None  # (gen, n_pad floor)
        # guards _rows/_masks/_row_bytes/_npad_hwm: concurrent readers
        # share one engine per shard (shard.object_search takes no lock on
        # the read path), and two threads evicting at once must not race
        # the pops or drift the byte accounting
        self._cache_lock = threading.RLock()
        self._jax = None  # lazy import: module import must not init backend
        # shape of the most recent search_batch dispatch as a shared
        # cost-model shape (monitoring/costmodel.py) — flops = 2·Q·U·n_pad per matmul sweep,
        # HBM traffic = the [U, n_pad] f32 row matrix read once
        self.last_batch_shape: Optional[costmodel.DispatchShape] = None

    # -- plumbing ------------------------------------------------------------

    def _backend(self):
        if self._jax is None:
            import jax  # noqa: PLC0415

            from weaviate_tpu.ops import bm25_scan  # noqa: PLC0415

            jax.devices()  # raises if no backend comes up
            self._jax = (jax, bm25_scan)
        return self._jax

    def _gen(self):
        return self._gen_fn() if self._gen_fn is not None else None

    def _npad(self, max_id: int, gen) -> int:
        """Dense-row length for this request: the bucket for max_id, but
        never below the generation's high-water mark — without the
        monotone floor, queries alternating between low-id and high-id
        terms would invalidate each other's cached rows (n_pad is part of
        the row-cache hit check) and re-scatter every time."""
        from weaviate_tpu.ops import bm25_scan  # noqa: PLC0415

        want = bm25_scan.n_bucket(max_id)
        with self._cache_lock:
            cur = self._npad_hwm
            if cur is not None and cur[0] == gen:
                want = max(want, cur[1])
                self._npad_hwm = (gen, want)
            elif cur is None or self._gen() == gen:
                # only the LIVE generation may reset the floor — a
                # straggler from an older generation must not clobber the
                # newer generation's high-water mark
                self._npad_hwm = (gen, want)
        return want

    def _evict_dead(self) -> None:
        """Drop rows/masks whose generation is no longer LIVE before
        building new ones (the old generation's device memory must be
        reclaimable NOW — a reindex sweep would otherwise double the
        footprint). Compares against the generation read at eviction time,
        NOT a caller-supplied one: an in-flight query that captured the
        previous generation must never wipe the current generation's
        cache."""
        live = self._gen()
        with self._cache_lock:
            dead = [k for k, v in self._rows.items() if v[0] != live]
            for k in dead:
                entry = self._rows.pop(k, None)
                if entry is not None:
                    self._row_bytes -= entry[2].nbytes
            self._masks = {k: v for k, v in self._masks.items()
                           if v[0] == live}

    # -- dense row cache -----------------------------------------------------

    def _dense_row(self, unit, n_pad: int, gen):
        """Fully-scaled dense impact row for one scoring unit, built on
        device and cached under the write generation."""
        jax, bm25_scan = self._backend()
        import jax.numpy as jnp  # noqa: PLC0415

        key = (unit.prop, unit.term, unit.weight)
        with self._cache_lock:
            hit = self._rows.get(key)
            if hit is not None and hit[0] == gen and hit[1] == n_pad:
                self._rows.move_to_end(key)
                return hit[2]
        # full per-posting scores, host side (f64 math, one pass) — the
        # scatter into doc-id space is the device's job. Built OUTSIDE the
        # lock: two threads may redundantly build the same row (last write
        # wins), but a slow scatter never blocks other queries' cache hits.
        scores = unit._score(unit.ids, unit.tf).astype(np.float32)
        ids = unit.ids.astype(np.int64)
        ids = np.where(ids < n_pad, ids, n_pad).astype(np.int32)
        ids, scores = bm25_scan.pad_postings(ids, scores, n_pad)
        zeros = jnp.zeros((n_pad + 1,), jnp.float32)
        row = bm25_scan.build_dense_row(
            jnp.asarray(ids), jnp.asarray(scores), zeros)
        if gen is not None and self._gen() == gen:
            with self._cache_lock:
                old = self._rows.pop(key, None)
                if old is not None:
                    self._row_bytes -= old[2].nbytes
                self._rows[key] = (gen, n_pad, row)
                self._row_bytes += row.nbytes
                while self._row_bytes > _ROW_CACHE_MAX_BYTES \
                        and len(self._rows) > 1:
                    _, (_, _, e) = self._rows.popitem(last=False)
                    self._row_bytes -= e.nbytes
        return row

    def _allow_mask(self, allow_list: AllowList, n_pad: int, gen):
        jax, _ = self._backend()
        import jax.numpy as jnp  # noqa: PLC0415

        # keyed by the Bitmap's identity, with the Bitmap itself PINNED in
        # the entry: without the strong ref, an evicted/uncached filter's
        # Bitmap could be freed and a different filter's Bitmap could
        # recycle the same address within one generation — the hit check
        # compares the stored object so a recycled id can never alias
        key = id(allow_list)
        with self._cache_lock:
            hit = self._masks.get(key)
            if hit is not None and hit[0] == gen and hit[1] == n_pad \
                    and hit[3] is allow_list:
                return hit[2]
        host = np.zeros((n_pad,), dtype=bool)
        ids = allow_list.to_array().astype(np.int64)
        host[ids[ids < n_pad]] = True
        mask = jnp.asarray(host)
        if gen is not None and self._gen() == gen:
            with self._cache_lock:
                if len(self._masks) >= 16:
                    self._masks.pop(next(iter(self._masks)), None)
                self._masks[key] = (gen, n_pad, mask, allow_list)
        return mask

    # -- search --------------------------------------------------------------

    def search(
        self,
        query: str,
        limit: int,
        properties: Optional[Sequence[str]] = None,
        allow_list: Optional[AllowList] = None,
        additional_explanations: bool = False,
    ) -> list[tuple[int, float, Optional[dict]]]:
        """Same contract as BM25Searcher.search. Explanations and device
        init failures fall back to the host engine."""
        if additional_explanations or limit <= 0:
            return self.searcher.search(
                query, limit, properties=properties, allow_list=allow_list,
                additional_explanations=additional_explanations)
        s = self.searcher
        props = s._searchable_props(properties)
        if any(w <= 0 for _, w in props):
            # non-positive boosts ("prop^0", "prop^-1") break the
            # score-0-means-empty sentinel the device packing relies on —
            # the host engine ranks them correctly, so it serves them
            return s.search(query, limit, properties=properties,
                            allow_list=allow_list)
        # gen BEFORE _doc_count/_build_units: the _dense_row insert guard
        # re-reads the generation after compute, so the guarded window must
        # span EVERYTHING idf depends on — captured after the count, a
        # write landing in between could pin stale-idf rows under the new
        # generation and serve them until the next write
        gen = self._gen()
        n_docs = max(s._doc_count(), 1)
        units = s._build_units(query, props, n_docs)
        if not units:
            return []
        total_postings = sum(u.ids.size for u in units)
        if total_postings < DEVICE_MIN_POSTINGS:
            return s.search(query, limit, properties=properties,
                            allow_list=allow_list)
        try:
            jax, bm25_scan = self._backend()
            import jax.numpy as jnp  # noqa: PLC0415
        except Exception as e:
            # a dead backend silently serving every keyword query at host
            # speed is a regression nobody sees — count
            # it and log (rate-limited) before degrading
            record_device_fallback("bm25_device.search", "backend_init", e)
            return s.search(query, limit, properties=properties,
                            allow_list=allow_list)

        max_id = max(int(u.ids[-1]) for u in units)  # ids are doc-sorted
        n_pad = self._npad(max_id, gen)
        self._evict_dead()
        total = self._dense_row(units[0], n_pad, gen)
        for u in units[1:]:
            total = bm25_scan.add_rows(total, self._dense_row(u, n_pad, gen))
        mask = self._allow_mask(allow_list, n_pad, gen) \
            if allow_list is not None else None
        k = min(bm25_scan.k_bucket(limit), n_pad)
        packed = bm25_scan.dense_topk(total, k, mask)
        scores, ids = bm25_scan.unpack_topk(packed, k)  # ONE blocking fetch
        scores = scores[:limit]
        ids = ids[:limit]
        keep = ids >= 0
        return [(int(d), float(v), None)
                for d, v in zip(ids[keep], scores[keep])]

    def search_batch(
        self,
        queries: Sequence[str],
        limit: int,
        properties: Optional[Sequence[str]] = None,
    ) -> Optional[list[list[tuple[int, float, None]]]]:
        """Q plain keyword queries in ONE device dispatch + ONE fetch:
        stack the distinct units' dense rows [U, n], build a [Q, U]
        selection matrix host-side, and let batch_topk's matmul produce
        every query's top-k. Returns None when the device path is
        unavailable (callers fall back to per-query host scoring).
        No allowList/explanations here — those park a query outside the
        batch lane (usecases/traverser.py get_class_batched eligibility)."""
        # cleared on EVERY path that doesn't dispatch: a caller reading
        # stats after a fallback must see None, not a previous batch's shape
        self.last_batch_shape = None
        if limit <= 0:
            return [[] for _ in queries]
        try:
            jax, bm25_scan = self._backend()
            import jax.numpy as jnp  # noqa: PLC0415
        except Exception as e:
            record_device_fallback("bm25_device.search_batch", "backend_init",
                                   e, note="batch lane falls back to "
                                   "per-query host scoring")
            return None
        s = self.searcher
        props = s._searchable_props(properties)
        if any(w <= 0 for _, w in props):
            return None  # non-positive boosts: host engine (see search())
        gen = self._gen()  # before _doc_count — same window as search()
        n_docs = max(s._doc_count(), 1)
        per_query_units = [s._build_units(q, props, n_docs) for q in queries]
        all_units = [u for units in per_query_units for u in units]
        if not all_units:
            return [[] for _ in queries]
        max_id = max(int(u.ids[-1]) for u in all_units)
        n_pad = self._npad(max_id, gen)
        self._evict_dead()
        # greedy slicing under the transient-stack budget: each slice's
        # DISTINCT units fit _BATCH_STACK_MAX_BYTES once stacked; a slice
        # still amortizes its dispatch+fetch over many queries
        max_units = max(int(_BATCH_STACK_MAX_BYTES // (n_pad * 4)),
                        max(len(u) for u in per_query_units), 1)
        out: list[list[tuple[int, float, None]]] = []
        stats = {"q": len(queries), "u": 0, "n_pad": n_pad, "slices": 0,
                 "qu": 0}  # qu = sum over slices of q_slice*u_slice
        qi = 0
        while qi < len(queries):
            ukeys: dict[tuple, object] = {}
            slice_units: list = []
            j = qi
            while j < len(queries):
                units = per_query_units[j]
                new = {(u.prop, u.term, u.weight): u for u in units
                       if (u.prop, u.term, u.weight) not in ukeys}
                if ukeys and len(ukeys) + len(new) > max_units:
                    break
                ukeys.update(new)
                slice_units.append(units)
                j += 1
            out.extend(self._matmul_slice(
                slice_units, ukeys, n_pad, gen, limit, jnp, bm25_scan))
            stats["u"] += len(ukeys)
            stats["qu"] += len(slice_units) * len(ukeys)
            stats["slices"] += 1
            qi = j
        # flops = 2 * n_pad * sum(q_slice*u_slice): a multi-slice sweep
        # does NOT multiply every query by every slice's units, so the
        # effective per-query unit width is qu/q
        self.last_batch_shape = costmodel.DispatchShape(
            costmodel.TIER_BM25_MATMUL,
            n=stats["n_pad"],
            dim=stats["qu"] / max(stats["q"], 1),
            batch=stats["q"],
            bytes_per_row=stats["u"] * 4,
            k=int(limit),
            extra=stats)
        return out

    @property
    def last_batch_stats(self) -> Optional[dict]:
        """Flat dict view of the last batch dispatch's shape (the
        pre-costmodel field name; bench rows and tests read it)."""
        s = self.last_batch_shape
        return None if s is None else s.describe()

    def _matmul_slice(self, per_query_units, ukeys, n_pad, gen, limit,
                      jnp, bm25_scan):
        """One batch_topk dispatch + one fetch for a slice of queries whose
        distinct units are already bounded by the caller."""
        if not ukeys:
            return [[] for _ in per_query_units]
        rows = [self._dense_row(u, n_pad, gen) for u in ukeys.values()]
        u_pad = bm25_scan.k_bucket(len(rows))
        if u_pad > len(rows):
            zero = jnp.zeros((n_pad,), jnp.float32)
            rows.extend([zero] * (u_pad - len(rows)))
        upos = {key: i for i, key in enumerate(ukeys)}
        qc = bm25_scan._QCHUNK
        q_pad = -(-len(per_query_units) // qc) * qc
        sel = np.zeros((q_pad, u_pad), dtype=np.float32)
        for qi, units in enumerate(per_query_units):
            for u in units:
                # += not =: a repeated property (["body", "body"]) yields
                # duplicate units that the per-query paths score twice
                sel[qi, upos[(u.prop, u.term, u.weight)]] += 1.0
        k = min(bm25_scan.k_bucket(limit), n_pad)
        packed = bm25_scan.batch_topk(jnp.stack(rows), jnp.asarray(sel), k)
        scores_all, ids_all = bm25_scan.topk_ops.unpack_topk(
            np.asarray(packed))  # ONE blocking fetch for the slice
        out: list[list[tuple[int, float, None]]] = []
        for qi in range(len(per_query_units)):
            scores = scores_all[qi][:limit]
            ids = ids_all[qi][:limit]
            keep = ids >= 0
            out.append([(int(d), float(v), None)
                        for d, v in zip(ids[keep], scores[keep])])
        return out
