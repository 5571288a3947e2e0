"""CSR segment: the TPU-native replacement for the reference's hash-table store.

The reference stores edges in a cluster-chaining hash table keyed by
(vid, pid, dir) (core/store/gstore.hpp:55-120) and probes it per row. Pointer
chasing is hostile to a vector unit, so we keep the reference's *segment*
abstraction (one segment per (pid, dir) — core/store/meta.hpp:78-142) but encode
each segment as CSR: a sorted unique key array + offsets + edge array. Lookup is
a binary search (host: np.searchsorted; device: vectorized searchsorted/gather),
which is what the reference's GPU engine approximates with block-mapped hash
probes (core/gpu/gpu_hash.cu:149-260).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

import numpy as np


@dataclass
class CSRSegment:
    keys: np.ndarray  # [K] sorted unique vertex ids
    offsets: np.ndarray  # [K+1] int64 prefix offsets into edges
    edges: np.ndarray  # [E] neighbor ids, sorted within each key's range

    @staticmethod
    def empty(dtype=np.int64) -> "CSRSegment":
        return CSRSegment(
            keys=np.empty(0, dtype=dtype),
            offsets=np.zeros(1, dtype=np.int64),
            edges=np.empty(0, dtype=dtype),
        )

    @staticmethod
    def from_pairs(k: np.ndarray, v: np.ndarray) -> "CSRSegment":
        """Build from parallel (key, value) arrays; sorts by (key, value), dedups pairs."""
        if len(k) == 0:
            return CSRSegment.empty(k.dtype if len(k) else np.int64)
        order = np.lexsort((v, k))
        return CSRSegment.from_sorted_pairs(k[order], v[order])

    @staticmethod
    def from_sorted_pairs(k: np.ndarray, v: np.ndarray) -> "CSRSegment":
        """Build from arrays already sorted by (key, value); dedups pairs."""
        if len(k) == 0:
            return CSRSegment.empty(np.int64)
        # drop duplicate (k, v) pairs (the reference dedups at insert for some paths)
        keep = np.ones(len(k), dtype=bool)
        keep[1:] = (k[1:] != k[:-1]) | (v[1:] != v[:-1])
        k, v = k[keep], v[keep]
        keys, counts = np.unique(k, return_counts=True)
        offsets = np.zeros(len(keys) + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        return CSRSegment(keys=keys, offsets=offsets, edges=v)

    @property
    def num_keys(self) -> int:
        return len(self.keys)

    @property
    def num_edges(self) -> int:
        return len(self.edges)

    @cached_property
    def max_degree(self) -> int:
        """The longest edge list of any key; one pass over the offsets,
        kept with the segment (a write makes a new segment)."""
        return int(np.diff(self.offsets).max()) if len(self.keys) else 0

    def lookup(self, vid: int) -> np.ndarray:
        """Edge list of one key (empty if absent) — GStore::get_edges analogue."""
        i = np.searchsorted(self.keys, vid)
        if i < len(self.keys) and self.keys[i] == vid:
            return self.edges[self.offsets[i]:self.offsets[i + 1]]
        return self.edges[0:0]

    def lookup_many(self, vids: np.ndarray):
        """Vectorized lookup: returns (start, degree) per query vid (0 deg if absent)."""
        if len(self.keys) == 0:
            z = np.zeros(len(vids), dtype=np.int64)
            return z, z.copy()
        idx = np.searchsorted(self.keys, vids)
        idx_c = np.clip(idx, 0, len(self.keys) - 1)
        found = (idx < len(self.keys)) & (self.keys[idx_c] == vids)
        start = np.where(found, self.offsets[idx_c], 0)
        deg = np.where(found, self.offsets[idx_c + 1] - self.offsets[idx_c], 0)
        return start, deg

    def contains_pair(self, vids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        """Vectorized membership: is `vals[i]` among the edges of `vids[i]`?

        Uses per-row binary search over the (sorted) edge range of each key —
        the k2k/k2c membership kernel (sparql.hpp:416-483) vectorized.
        """
        start, deg = self.lookup_many(vids)
        lo = start.astype(np.int64)
        end = (start + deg).astype(np.int64)
        hi = end.copy()
        if len(self.edges) == 0:
            return np.zeros(len(vids), dtype=bool)
        # branchless lower_bound over each row's ragged [start, end) range
        while True:
            active = lo < hi
            if not active.any():
                break
            mid = (lo + hi) // 2
            mv = self.edges[np.clip(mid, 0, len(self.edges) - 1)]
            less = mv < vals
            lo = np.where(active & less, mid + 1, lo)
            hi = np.where(active & ~less, mid, hi)
        inb = lo < end
        return inb & (self.edges[np.clip(lo, 0, len(self.edges) - 1)] == vals)

    def memory_bytes(self) -> int:
        return self.keys.nbytes + self.offsets.nbytes + self.edges.nbytes
