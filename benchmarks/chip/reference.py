"""Plain reference triad census, in numpy alone.

It imports nothing of the program under test and takes nothing that the
program made: it reads the raw arc list the generator produced.  The
census is counted by wedges (paths of length two in the undirected
graph underneath), the textbook decomposition:

* every triad with two connected dyads is one *open* wedge, found once,
  at its centre;
* every triad with three connected dyads is a triangle, found once at
  each of its three corners;
* a triad with one connected dyad is counted from the dyad counts: each
  connected pair meets ``n - 2`` third vertices, and each triad with
  ``k`` connected dyads takes ``k`` of those meetings;
* ``003`` is what remains of ``C(n, 3)``.

Counts are exact in int64.  :func:`census` takes ``dtype=np.float32`` to
carry the counts in float32 instead: that is the control, the census as
a program that accumulates in the nearest precision below int64 would
give it.
"""

from __future__ import annotations

import itertools

import numpy as np

#: Holland-Leinhardt names, in the standard order of the 16 classes
NAMES = ("003", "012", "102", "021D", "021U", "021C", "111D", "111U",
         "030T", "030C", "201", "120D", "120U", "120C", "210", "300")

#: wedges classified per block of centres (bounds the working memory)
BLOCK_WEDGES = 1 << 22


def classify(adj: np.ndarray) -> int:
    """Class index of a 3-vertex digraph given as a 3x3 0/1 matrix, by
    its mutual/asymmetric/null dyad counts and, where those tie, by
    where the asymmetric arcs point."""
    a = adj.astype(bool)
    pairs = ((0, 1), (0, 2), (1, 2))
    mut = [p for p in pairs if a[p] and a[p[::-1]]]
    asym = [(i, j) if a[i, j] else (j, i) for i, j in pairs
            if a[i, j] != a[j, i]]
    m, s = len(mut), len(asym)
    outdeg = [sum(1 for x, _ in asym if x == v) for v in range(3)]
    indeg = [sum(1 for _, y in asym if y == v) for v in range(3)]
    if (m, s) == (0, 0):
        return 0
    if (m, s) == (0, 1):
        return 1
    if (m, s) == (1, 0):
        return 2
    if (m, s) == (0, 2):
        if 2 in outdeg:
            return 3                       # 021D: a <- b -> c
        if 2 in indeg:
            return 4                       # 021U: a -> b <- c
        return 5                           # 021C: a -> b -> c
    if (m, s) == (1, 1):
        (x, y), = asym
        in_mutual = set(mut[0])
        return 6 if y in in_mutual else 7  # 111D: into the mutual dyad
    if (m, s) == (0, 3):
        return 8 if 2 in outdeg else 9     # 030T transitive, 030C cycle
    if (m, s) == (2, 0):
        return 10
    if (m, s) == (1, 2):
        if 2 in outdeg:
            return 11                      # 120D: c sends to both
        if 2 in indeg:
            return 12                      # 120U: both send to c
        return 13                          # 120C
    if (m, s) == (2, 1):
        return 14
    return 15                              # 300


def _dyad(a: np.ndarray, i: int, j: int) -> int:
    return int(a[i, j]) | (int(a[j, i]) << 1)


def _class_table() -> np.ndarray:
    """Class of every wedge code ``c_va * 16 + c_vb * 4 + c_ab``, where
    ``c_xy = (x -> y) | (y -> x) << 1`` and vertex 0 is the centre."""
    table = np.zeros(64, np.int64)
    for bits in itertools.product((0, 1), repeat=6):
        a = np.zeros((3, 3), np.int64)
        a[0, 1], a[1, 0], a[0, 2], a[2, 0], a[1, 2], a[2, 1] = bits
        code = _dyad(a, 0, 1) * 16 + _dyad(a, 0, 2) * 4 + _dyad(a, 1, 2)
        table[code] = classify(a)
    return table


CLASS_OF_CODE = _class_table()
#: mutual and asymmetric dyads of each class
MUTUAL = np.array([0, 0, 1, 0, 0, 0, 1, 1, 0, 0, 2, 1, 1, 1, 2, 3])
ASYM = np.array([0, 1, 0, 2, 2, 2, 1, 1, 3, 3, 0, 2, 2, 2, 1, 0])
CONNECTED = MUTUAL + ASYM


def dyads(src, dst, n: int):
    """Connected dyads of an arc list: sorted keys ``lo * n + hi`` of the
    unordered pairs and their codes (bit 0: lo -> hi, bit 1: hi -> lo).
    Self-loops are dropped; repeated arcs count once."""
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    keep = src != dst
    src, dst = src[keep], dst[keep]
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    bit = np.where(src < dst, 1, 2)
    key = lo * n + hi
    order = np.argsort(key, kind="stable")
    key, bit = key[order], bit[order]
    first = np.ones(key.shape[0], bool)
    first[1:] = key[1:] != key[:-1]
    starts = np.nonzero(first)[0]
    code = np.bitwise_or.reduceat(bit, starts) if starts.size else bit[:0]
    return key[starts], code.astype(np.int64)


def _adjacency(pkey: np.ndarray, pcode: np.ndarray, n: int):
    """Undirected CSR over the dyads: neighbours sorted per row, and the
    dyad code as seen from the row's vertex."""
    lo, hi = pkey // n, pkey % n
    swap = ((pcode & 1) << 1) | (pcode >> 1)
    row = np.concatenate([lo, hi])
    col = np.concatenate([hi, lo])
    code = np.concatenate([pcode, swap])
    order = np.lexsort((col, row))
    row, col, code = row[order], col[order], code[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(row, minlength=n), out=indptr[1:])
    return indptr, col, code


def _wedge_histogram(pkey, pcode, n: int) -> np.ndarray:
    """Histogram of wedge codes over every wedge of the graph."""
    indptr, col, code = _adjacency(pkey, pcode, n)
    deg = np.diff(indptr)
    per = deg * (deg - 1) // 2
    hist = np.zeros(64, np.int64)
    centres = np.nonzero(per)[0]
    if centres.size == 0:
        return hist
    cum = np.cumsum(per[centres])
    start = 0
    while start < centres.size:
        base = cum[start - 1] if start else 0
        stop = int(np.searchsorted(cum, base + BLOCK_WEDGES,
                                   side="right"))
        stop = max(stop, start + 1)
        block = centres[start:stop]
        counts = per[block]
        v = np.repeat(block, counts)
        first = np.repeat(np.cumsum(counts) - counts, counts)
        t = np.arange(v.shape[0], dtype=np.int64) - first
        # t-th pair (i, j), i < j, of the row, enumerated by j
        j = ((1 + np.sqrt(1 + 8 * t.astype(np.float64))) // 2
             ).astype(np.int64)
        j -= (j * (j - 1) // 2 > t)
        j += ((j + 1) * j // 2 <= t)
        i = t - j * (j - 1) // 2
        ea, eb = indptr[v] + i, indptr[v] + j
        a, b = col[ea], col[eb]
        c_va, c_vb = code[ea], code[eb]
        # the dyad between the two ends, seen from a
        k = np.minimum(a, b) * n + np.maximum(a, b)
        pos = np.minimum(np.searchsorted(pkey, k), pkey.shape[0] - 1)
        found = pkey[pos] == k
        c = np.where(found, pcode[pos], 0)
        c_ab = np.where(a < b, c, ((c & 1) << 1) | (c >> 1))
        hist += np.bincount(c_va * 16 + c_vb * 4 + c_ab, minlength=64)
        start = stop
    return hist


def census(src, dst, n: int, dtype=np.int64) -> np.ndarray:
    """Exact 16-class triad census of the digraph with these arcs on
    ``n`` vertices (int64).  ``dtype=np.float32`` carries every count
    and every step of the closed forms in float32: the control."""
    pkey, pcode = dyads(src, dst, n)
    hist = _wedge_histogram(pkey, pcode, n).astype(dtype)
    by_class = np.zeros(16, dtype)
    np.add.at(by_class, CLASS_OF_CODE, hist)
    out = np.zeros(16, dtype)
    tri = CONNECTED == 3
    out[CONNECTED == 2] = by_class[CONNECTED == 2]
    out[tri] = by_class[tri] // 3 if dtype == np.int64 \
        else by_class[tri] / dtype(3)
    third = dtype(n - 2)
    mutual = dtype(int(np.count_nonzero(pcode == 3)))
    asym = dtype(int(pcode.shape[0])) - mutual
    many = CONNECTED >= 2
    out[2] = mutual * third - (out[many] * MUTUAL[many].astype(dtype)).sum()
    out[1] = asym * third - (out[many] * ASYM[many].astype(dtype)).sum()
    total = dtype(n * (n - 1) * (n - 2) // 6)
    out[0] = total - out[1:].sum()
    return out


def gap(got: np.ndarray, want: np.ndarray) -> int:
    """Widest gap between two censuses, over the 16 classes, as an exact
    integer (a float census is compared at its exact value)."""
    got = [int(x) for x in np.asarray(got).tolist()]
    want = [int(x) for x in np.asarray(want).tolist()]
    return max(abs(a - b) for a, b in zip(got, want))


def census_job(args) -> np.ndarray:
    """:func:`census` of one ``(src, dst, n)``, for a pool of worker
    processes."""
    return census(*args)
