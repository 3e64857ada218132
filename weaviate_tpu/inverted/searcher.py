"""Filter evaluation: where-clause tree -> doc-ID Bitmap (AllowList).

Reference: inverted/searcher.go:157 (DocIDs) + searcher_doc_bitmap.go:25-109
(per-clause docBitmap, sroar AND/OR/AndNot merges) + like_regexp.go.

Operator semantics (entities/filters/filters.go:24-35):
Equal / NotEqual / GreaterThan(Equal) / LessThan(Equal) / Like / IsNull /
ContainsAny / ContainsAll / WithinGeoRange + And / Or / Not combinators.
Range operators run as lexicographic key-range scans over the byte-sortable
token keys (analyzer.encode_*).
"""

from __future__ import annotations

import bisect
import re
from typing import Callable, Optional

from weaviate_tpu.entities.filters import (
    Clause,
    FilterValidationError,
    GeoRange,
    LocalFilter,
    Operator,
    like_to_regex,
)
from weaviate_tpu.entities.schema import ClassDef, DataType
from weaviate_tpu.inverted.analyzer import filter_value_token
from weaviate_tpu.inverted.index import (
    ALL_DOCS_KEY,
    NULL_TRUE,
    InvertedIndex,
    filterable_bucket,
)
from weaviate_tpu.storage.bitmap import Bitmap


class PostingMemo:
    """The postings read during ONE `filter` phase (db/shard.py opens it
    for a group of filtered slots and drops it with the phase): a leaf that
    asks a (bucket, token) another filter of the group already read gets
    the same Bitmap, which is immutable. It sits below the tree, so every
    filter shape gains and none is tested for; it outlives no phase, so a
    write acknowledged before the next group is read by it. Leaving the
    `with` block ends it: a posting that is also a slot's allowList lives
    on as that, without the bitset its intersections made (Bitmap.and_)."""

    __slots__ = ("_got", "hits", "ids")

    def __init__(self):
        self._got: dict[tuple[object, bytes], Bitmap] = {}
        self.hits = 0   # leaf reads served from the memo
        self.ids = 0    # ids read from the buckets

    def __len__(self) -> int:
        """Distinct postings read."""
        return len(self._got)

    def __enter__(self) -> "PostingMemo":
        return self

    def __exit__(self, *exc) -> None:
        for posting in self._got.values():
            posting.drop_bits()
        self._got.clear()

    def get(self, bucket, token: bytes) -> Bitmap:
        got = self._got.get((bucket, token))
        if got is None:
            got = self._got[(bucket, token)] = bucket.roaring_get(token)
            self.ids += len(got)
        else:
            self.hits += 1
        return got


def _posting(bucket, token: bytes, memo: Optional[PostingMemo]) -> Bitmap:
    return bucket.roaring_get(token) if memo is None else memo.get(bucket, token)


class FilterSearcher:
    def __init__(
        self,
        inverted: InvertedIndex,
        class_def: ClassDef,
        geo_search: Optional[Callable[[str, GeoRange], Bitmap]] = None,
        ref_resolver: Optional[Callable[[list[str], Clause], Bitmap]] = None,
    ):
        self.inverted = inverted
        self.class_def = class_def
        self.geo_search = geo_search
        self.ref_resolver = ref_resolver

    def doc_ids(self, flt: LocalFilter,
                memo: Optional[PostingMemo] = None) -> Bitmap:
        """`memo`: the group's, where `flt` is one of a group of filters
        resolved in one phase; every leaf read goes through it."""
        return self._eval(flt.root, memo)

    def _universe(self, memo: Optional[PostingMemo]) -> Bitmap:
        """Every live doc id (InvertedIndex.all_doc_ids, through the memo)."""
        return _posting(self.inverted._all, ALL_DOCS_KEY, memo)

    # -- tree ----------------------------------------------------------------

    def _eval(self, c: Clause, memo: Optional[PostingMemo]) -> Bitmap:
        if c.operator is Operator.AND:
            out: Optional[Bitmap] = None
            for op in c.operands:
                b = self._eval(op, memo)
                out = b if out is None else out.and_(b)
            return out or Bitmap()
        if c.operator is Operator.OR:
            out = Bitmap()
            for op in c.operands:
                out = out.or_(self._eval(op, memo))
            return out
        if c.operator is Operator.NOT:
            # complement against the live universe (searcher uses the doc
            # universe the same way for NotEqual)
            universe = self._universe(memo)
            out = Bitmap()
            for op in c.operands:
                out = out.or_(self._eval(op, memo))
            return universe.and_not(out)
        return self._eval_value(c, memo)

    # -- leaves --------------------------------------------------------------

    def _prop(self, c: Clause):
        if not c.on:
            raise FilterValidationError("filter clause without path")
        name = c.on[0]
        if len(c.on) > 1:
            if name == "id" or name == "_id":
                raise FilterValidationError("id path cannot be nested")
            if self.ref_resolver is None:
                raise FilterValidationError("reference filters not supported here")
            return None  # handled by caller via ref path
        prop = self.class_def.get_property(name)
        if prop is None and name not in ("id", "_id", "_creationTimeUnix", "_lastUpdateTimeUnix"):
            raise FilterValidationError(f"unknown property {name!r} in filter")
        return prop

    def _eval_value(self, c: Clause, memo: Optional[PostingMemo]) -> Bitmap:
        if len(c.on) > 1:
            # cross-reference path: [RefProp, TargetClass, targetProp...]
            if self.ref_resolver is None:
                raise FilterValidationError("reference filters not supported")
            return self.ref_resolver(c.on, c)
        name = c.on[0]
        if name in ("id", "_id"):
            return self._eval_id(c)
        prop = self._prop(c)
        if prop is None:
            raise FilterValidationError(f"unknown property {name!r}")
        pt = prop.primitive_type()
        if pt is None:
            raise FilterValidationError(
                f"property {name!r} is a reference; use a nested path"
            )
        if c.operator is Operator.WITHIN_GEO_RANGE:
            if pt.base is not DataType.GEO_COORDINATES:
                raise FilterValidationError("WithinGeoRange needs a geoCoordinates property")
            if self.geo_search is None:
                raise FilterValidationError("geo index not available")
            return self.geo_search(name, c.value)
        if c.operator is Operator.IS_NULL:
            from weaviate_tpu.inverted.index import null_bucket

            nb = self.inverted.store.bucket(null_bucket(name))
            if nb is None:
                return Bitmap()
            nulls = _posting(nb, NULL_TRUE, memo)
            if c.value in (False, None) or (isinstance(c.value, bool) and not c.value):
                return self._universe(memo).and_not(nulls)
            return nulls
        if not prop.index_filterable:
            raise FilterValidationError(f"property {name!r} is not indexFilterable")
        bucket = self.inverted.store.bucket(filterable_bucket(name))
        if bucket is None:
            return Bitmap()

        if c.operator in (Operator.CONTAINS_ANY, Operator.CONTAINS_ALL):
            values = c.value if isinstance(c.value, list) else [c.value]
            out: Optional[Bitmap] = None
            for v in values:
                tok = filter_value_token(pt, prop.tokenization, v)
                b = _posting(bucket, tok, memo)
                if c.operator is Operator.CONTAINS_ANY:
                    out = b if out is None else out.or_(b)
                else:
                    out = b if out is None else out.and_(b)
            return out or Bitmap()

        if c.operator is Operator.LIKE:
            rx = re.compile(like_to_regex(str(c.value)).encode("utf-8"))
            out = Bitmap()
            for key in bucket.keys():
                if rx.match(key):
                    out = out.or_(_posting(bucket, key, memo))
            return out

        tok = filter_value_token(pt, prop.tokenization, c.value)
        if c.operator is Operator.EQUAL:
            return _posting(bucket, tok, memo)
        if c.operator is Operator.NOT_EQUAL:
            return self._universe(memo).and_not(_posting(bucket, tok, memo))
        if c.operator in (
            Operator.GREATER_THAN,
            Operator.GREATER_THAN_EQUAL,
            Operator.LESS_THAN,
            Operator.LESS_THAN_EQUAL,
        ):
            return self._range(bucket, tok, c.operator, memo)
        raise FilterValidationError(f"unsupported operator {c.operator}")

    def _range(self, bucket, tok: bytes, op: Operator,
               memo: Optional[PostingMemo]) -> Bitmap:
        keys = bucket.keys()
        lo = bisect.bisect_left(keys, tok)
        out = Bitmap()
        if op is Operator.GREATER_THAN:
            start = bisect.bisect_right(keys, tok)
            sel = keys[start:]
        elif op is Operator.GREATER_THAN_EQUAL:
            sel = keys[lo:]
        elif op is Operator.LESS_THAN:
            sel = keys[:lo]
        else:  # LESS_THAN_EQUAL
            sel = keys[: bisect.bisect_right(keys, tok)]
        for k in sel:
            out = out.or_(_posting(bucket, k, memo))
        return out

    def _eval_id(self, c: Clause) -> Bitmap:
        """id filters resolve through the uuid->docID mapping supplied by the
        shard (searcher_doc_bitmap uuid path). Requires an id_resolver."""
        raise FilterValidationError(
            "id-path filters must be evaluated by the shard (uuid index)"
        )
