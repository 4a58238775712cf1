"""A tree's in-memory side: the mutable memtable and the frozen run.

reference: src/lsm/table_memory.zig (the mutable table takes values in
arrival order and is sorted once, when it turns immutable).

The mutable memtable holds what arrived since the last freeze, in
arrival order: dicts (what `put` / `remove` feed, a key at a time) and
column runs (what `put_run` appends whole, no work per row). The newest
write of a key wins, a tombstone included, across dicts and runs alike.
A read by key or range folds the pending runs into one dict — it pays
per row what a `put` per row would have paid, once, and only in a tree
that is read. `freeze` sorts everything once with numpy into a
`SortedRun`: the rows `key || value` as one uint8 matrix, which the
flush job writes out a block-sized slice at a time."""

from __future__ import annotations

from typing import Optional

import numpy as np


def _key_words(keys: np.ndarray) -> np.ndarray:
    """uint8[n, key_size] -> native uint64[n, words] whose row-wise
    (word 0 first) order is the `bytes` order of the keys: big-endian
    words, a short last word padded with zeros on the right (every key
    of a tree has the same size, so the padding never decides)."""
    n, key_size = keys.shape
    words = -(-key_size // 8)
    if key_size % 8:
        padded = np.zeros((n, words * 8), dtype=np.uint8)
        padded[:, :key_size] = keys
    else:
        padded = np.ascontiguousarray(keys)
    return padded.view(">u8").astype(np.uint64)


class SortedRun:
    """Sorted rows of unique keys, `key || value`, as uint8[n, key_size +
    value_size]: the frozen memtable, readable by binary search while
    its flush job streams it into level-0 tables."""

    def __init__(self, rows: np.ndarray, key_size: int,
                 lookup: Optional[dict] = None):
        self.rows = rows
        self.key_size = key_size
        # {key: value} of the same rows where the memtable held them as
        # one dict anyway (a tree written a key at a time): point reads
        # stay a dict probe, as they were.
        self.lookup = lookup
        self._raw = memoryview(rows).cast("B") if len(rows) else b""

    def __len__(self) -> int:
        return len(self.rows)

    def key(self, i: int) -> bytes:
        start = i * self.rows.shape[1]
        return bytes(self._raw[start:start + self.key_size])

    def _bisect(self, key: bytes, right: bool = False) -> int:
        """First row whose key is >= `key` (> `key` with `right`)."""
        raw, entry, key_size = self._raw, self.rows.shape[1], self.key_size
        lo, hi = 0, len(self.rows)
        while lo < hi:
            mid = (lo + hi) // 2
            k = bytes(raw[mid * entry:mid * entry + key_size])
            if k < key or (right and k == key):
                lo = mid + 1
            else:
                hi = mid
        return lo

    def get(self, key: bytes) -> Optional[bytes]:
        if self.lookup is not None:
            return self.lookup.get(key)
        i = self._bisect(key)
        if i < len(self.rows) and self.key(i) == key:
            start = i * self.rows.shape[1]
            return bytes(self._raw[start + self.key_size:
                                   start + self.rows.shape[1]])
        return None

    def between(self, key_min: bytes, key_max: bytes) -> list:
        """[(key, value)] of the rows in [key_min, key_max], ascending."""
        entry, key_size = self.rows.shape[1], self.key_size
        lo = self._bisect(key_min)
        hi = self._bisect(key_max, right=True)
        raw = bytes(self._raw[lo * entry:hi * entry])
        return [(raw[p:p + key_size], raw[p + key_size:p + entry])
                for p in range(0, len(raw), entry)]


class Memtable:
    def __init__(self, key_size: int, value_size: int):
        self.key_size = key_size
        self.value_size = value_size
        # Arrival order. A dict, or a run: (keys uint8[n, key_size],
        # values uint8[n, value_size] or one value for every row).
        self._segments: list = []
        self._top: Optional[dict] = None  # the newest segment, if a dict
        self._runs = 0  # run segments among them
        # Rows of runs that a read made pay per key (cumulative).
        self.rows_folded = 0

    def __bool__(self) -> bool:
        return bool(self._segments)

    def put(self, key: bytes, value: bytes) -> None:
        top = self._top
        if top is None:
            top = self._top = {}
            self._segments.append(top)
        top[key] = value

    def put_run(self, keys, values) -> None:
        """Append a run whole. `keys`: uint8[n, key_size] or its bytes;
        `values`: uint8[n, value_size], or the one value of every row.
        The arrays are the memtable's from here on."""
        if not isinstance(keys, np.ndarray):
            keys = np.frombuffer(keys, dtype=np.uint8).reshape(
                -1, self.key_size)
        assert keys.dtype == np.uint8 and keys.ndim == 2 \
            and keys.shape[1] == self.key_size, (keys.dtype, keys.shape)
        if isinstance(values, np.ndarray):
            assert values.dtype == np.uint8 \
                and values.shape == (len(keys), self.value_size), \
                (values.dtype, values.shape)
        else:
            assert len(values) == self.value_size
        if len(keys):
            self._segments.append((keys, values))
            self._top = None
            self._runs += 1

    def clear(self) -> None:
        self._segments = []
        self._top = None
        self._runs = 0

    # --------------------------------------------------------------- reads

    def _fold(self) -> None:
        """Fold the runs in: everything as ONE dict, newest write last."""
        out: dict = {}
        ks, vs = self.key_size, self.value_size
        for i, seg in enumerate(self._segments):
            if isinstance(seg, dict):
                if i == 0:
                    out = seg  # the oldest: the others fold into it
                else:
                    out.update(seg)
                continue
            keys, values = seg
            raw = keys.tobytes()
            key_list = [raw[p:p + ks] for p in range(0, len(raw), ks)]
            if isinstance(values, np.ndarray):
                raw = values.tobytes()
                out.update(zip(key_list, [
                    raw[p:p + vs] for p in range(0, len(raw), vs)]))
            else:
                out.update(dict.fromkeys(key_list, bytes(values)))
            self.rows_folded += len(key_list)
        self._segments = [out]
        self._top = out
        self._runs = 0

    @property
    def pending_runs(self) -> int:
        """Run segments that the next read by key or range would fold."""
        return self._runs

    def fold(self) -> None:
        """Fold the pending runs now: what the next `get` or `between`
        would do first, for a caller that wants to time it apart."""
        if self._runs:
            self._fold()

    def get(self, key: bytes) -> Optional[bytes]:
        if self._runs:
            self._fold()
        # No runs: at most one segment, and it is `_top`.
        return None if self._top is None else self._top.get(key)

    def between(self, key_min: bytes, key_max: bytes) -> list:
        """[(key, value)] of the keys in [key_min, key_max], ascending
        (tombstones included)."""
        if self._runs:
            self._fold()
        return sorted((k, v) for k, v in (self._top or {}).items()
                      if key_min <= k <= key_max)

    # -------------------------------------------------------------- freeze

    def freeze(self) -> Optional[SortedRun]:
        """The sorted run of what the memtable holds (None when empty);
        the memtable itself is left as it was. One stable sort over all
        segments in arrival order, keeping the last of equal keys."""
        if not self._segments:
            return None
        ks, vs = self.key_size, self.value_size
        sizes = [len(seg) if isinstance(seg, dict) else len(seg[0])
                 for seg in self._segments]
        rows = np.empty((sum(sizes), ks + vs), dtype=np.uint8)
        start = 0
        for seg, n in zip(self._segments, sizes):
            part = rows[start:start + n]
            if isinstance(seg, dict):
                part[:, :ks] = np.frombuffer(
                    b"".join(seg), dtype=np.uint8).reshape(n, ks)
                part[:, ks:] = np.frombuffer(
                    b"".join(seg.values()), dtype=np.uint8).reshape(n, vs)
            else:
                keys, values = seg
                part[:, :ks] = keys
                part[:, ks:] = values if isinstance(values, np.ndarray) \
                    else np.frombuffer(values, dtype=np.uint8)
            start += n
        words = _key_words(rows[:, :ks])
        # lexsort's last key is the primary one: word 0.
        order = np.lexsort(words.T[::-1])
        if (order[1:] < order[:-1]).any():  # else: arrived sorted
            words = words[order]
            rows = rows[order]
        if len(rows) > 1:
            last = np.ones(len(rows), dtype=bool)
            last[:-1] = (words[1:] != words[:-1]).any(axis=1)
            if not last.all():
                rows = rows[last]
        return SortedRun(rows, ks,
                         lookup=self._top if not self._runs else None)
