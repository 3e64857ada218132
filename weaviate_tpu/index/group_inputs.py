"""The host half of a filtered group's dispatches (index/tpu.py
search_by_vectors_multi_async): from the group's allowLists to the operands
its programs are handed, each gather bucket's `rows [slots, r]` int32 with
`counts [slots]`, and the masked scan's `words [queries, capacity / 32]`.

One native pass (native/lsm_get.cpp lsm_group_locate, lsm_group_fill): the
distinct lists are located in the snapshot's docs (two binary searches a
list where doc ids are consecutive, the served case) and that gives every
list's size in slots, which is all the plan asks; once the plan is made the
rows and the mask bits are written straight into the operands. No int64
copy of the ids, no slot array a list, no bool mask. Docs that do not
ascend with their slots (a library caller that re-added a doc), a foreign
allowList or a missing library are served by the index's numpy
(`_allow_slots`, `_slot_words`: the single-filter path's, and the oracle of
tests/test_group_inputs_native.py), counted by reason. The slots are
computed without tombstone knowledge either way (`_allow_slots`' contract):
the dispatching snapshot masks its dead rows on the device.

The operands are pooled (`OperandPool`): 32 MB of words and up to 2 MB of
rows a bucket are not mapped afresh a request (PERF.md section 6, PR 28:
fresh pages cost the served path a fifth of its rate). A buffer is zeroed
only where its last use dirtied it, and it goes back to the pool in the
dispatch's finalize, AFTER the blocking fetch: until then the upload may
still read it, and a buffer refilled earlier is another request's mask.
Nothing here outlives a request but the buffers themselves: no list's
slots or words are kept.

What a group cost is `/debug/perf` `group_inputs` (monitoring/perf.py
note_group_inputs).
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence

import numpy as np

from weaviate_tpu.monitoring import perf
from weaviate_tpu.storage import lsm_native

# why a group's operands were built in numpy: lsm_native.group_locate's
# two reasons, and the snapshot's own
NO_LIBRARY = "no_library"
FOREIGN_LIST = "foreign_list"    # an allowList that is no uint64 array
DOC_ORDER = "doc_order"          # docs do not ascend with their slots

_KIND_ROWS, _KIND_WORDS = 0, 1


class Operand:
    """One pooled operand: `arr` is what the program is handed (int32
    `rows` or uint32 `words`), `counts` a gather bucket's second operand
    and, between uses, the record of how far each row was written; `dirty`
    the rows of `words` the last use set bits in."""

    __slots__ = ("kind", "arr", "counts", "dirty")

    def __init__(self, kind: int, shape: tuple):
        rows = kind == _KIND_ROWS
        self.kind = kind
        self.arr = np.zeros(shape, np.int32 if rows else np.uint32)
        self.counts = np.zeros(shape[0], np.int32) if rows else None
        self.dirty = 0

    @property
    def nbytes(self) -> int:
        return self.arr.nbytes + (self.counts.nbytes if self.kind
                                  == _KIND_ROWS else 0)


class OperandPool:
    """The index's free operands by (kind, shape), under the lock of its
    query staging pool (`lock`). `take` hands out a parked buffer or a
    fresh zeroed one; `give` parks it, at most `cap` a shape (the live
    pipeline depth, as the staging pool)."""

    def __init__(self, cap: int, lock):
        self._cap = cap
        self._lock = lock
        self._free: dict[tuple, list[Operand]] = {}

    def take(self, kind: int, shape: tuple) -> tuple[Operand, bool]:
        """-> (operand, whether it came from the pool)."""
        with self._lock:
            parked = self._free.get((kind, shape))
            op = parked.pop() if parked else None
            if kind == _KIND_WORDS:
                # words of another width are a smaller capacity's: the
                # index grew, nothing will ask for them again
                for key in [k for k in self._free
                            if k[0] == kind and k[1][1] != shape[1]]:
                    del self._free[key]
        if op is not None:
            return op, True
        return Operand(kind, shape), False

    def give(self, op: Operand,
             alive: Optional[Callable[[], bool]] = None) -> None:
        """Park `op`, unless `alive`, asked under the lock, says the pool's
        owner has dropped its data since."""
        with self._lock:
            if alive is not None and not alive():
                return
            parked = self._free.setdefault((op.kind, op.arr.shape), [])
            if len(parked) < self._cap:
                parked.append(op)

    def clear(self) -> None:
        with self._lock:
            self._free.clear()

    def nbytes(self) -> int:
        with self._lock:
            return sum(op.nbytes for parked in self._free.values()
                       for op in parked)


class GroupInputs:
    """A group's allowLists resolved against one snapshot. `list_of[i]` is
    slot i's index among the distinct lists (-1: no filter; equal allowList
    OBJECTS are one list), `sizes[l]` list l's store slots in the snapshot.
    `fill` then builds the operands of the planned dispatches."""

    def __init__(self, snap, allow_lists: Sequence,
                 allow_slots: Callable, slot_words: Callable):
        t0 = time.perf_counter()
        self._capacity, self._slot_words = snap.capacity, slot_words
        index: dict[int, int] = {}
        lists: list = []
        self.list_of: list[int] = []
        for a in allow_lists:
            at = -1 if a is None else index.get(id(a))
            if at is None:
                at = index[id(a)] = len(lists)
                lists.append(a)
            self.list_of.append(at)
        self._native, self._slots = None, None
        docs = snap.slot_to_doc[: snap.n]
        got = DOC_ORDER if not snap.docs_ascending else \
            lsm_native.group_locate(
                [a.to_array() for a in lists], docs,
                bool(snap.n) and int(docs[-1]) - int(docs[0]) == snap.n - 1)
        # why numpy builds this group's operands, None where it does not
        self.reason = got if isinstance(got, str) else None
        if self.reason is None:
            self._native = got
            self.sizes = got.sizes
            self.ids = got.walked
        else:
            self._slots = [allow_slots(snap, a) for a in lists]
            self.sizes = np.fromiter((s.size for s in self._slots), np.int64,
                                     len(lists))
            self.ids = int(sum(len(a) for a in lists))
        self.lists = len(lists)
        self._host_s = time.perf_counter() - t0

    def fill(self, pool: OperandPool,
             jobs: Sequence[tuple]) -> list[Operand]:
        """The operands of `jobs`, each (gather?, slot positions, height,
        width): a gather bucket's `rows [height, width]` with its counts,
        or the scan's `words [height, width]`, row j from the list of slot
        `positions[j]`. One native call for all of them."""
        t0 = time.perf_counter()
        ops, sels, table, hits = [], [], [], 0
        for gather, sel, height, width in jobs:
            op, hit = pool.take(_KIND_ROWS if gather else _KIND_WORDS,
                                (height, width))
            hits += hit
            ops.append(op)
            sels.append(np.fromiter((self.list_of[i] for i in sel), np.int64,
                                    len(sel)))
            table.append((op.kind, op.arr.ctypes.data,
                          op.counts.ctypes.data if gather else op.dirty,
                          height, width, len(sel)))
        if self._native is not None:
            self._native.fill(
                np.array(table, np.int64).reshape(len(jobs), 6),
                np.concatenate(sels) if sels else np.zeros(0, np.int64))
        for op, sel in zip(ops, sels):
            if self._native is None:
                (self._fill_rows if op.kind == _KIND_ROWS
                 else self._fill_words)(op, sel)
            if op.kind == _KIND_WORDS:
                op.dirty = len(sel)
        perf.note_group_inputs(
            self.lists, self.ids, self.reason,
            (self._host_s + time.perf_counter() - t0) * 1000.0,
            hits, len(ops) - hits)
        return ops

    # -- the numpy twin of lsm_group_fill -----------------------------------

    def _fill_rows(self, op: Operand, sel: np.ndarray) -> None:
        rows, counts = op.arr, op.counts
        for j in np.flatnonzero(counts):
            rows[j, : counts[j]] = 0
        counts[:] = 0
        for j, l in enumerate(sel):
            sl = self._slots[l]
            rows[j, : sl.size] = sl
            counts[j] = sl.size

    def _fill_words(self, op: Operand, sel: np.ndarray) -> None:
        words = op.arr
        words[: op.dirty] = 0
        for j, l in enumerate(sel):
            self._slot_words(self._slots[l], self._capacity, out=words[j])
