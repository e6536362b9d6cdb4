"""Build training inputs from telemetry records — vectorized and incremental.

Reference context: the scheduler streams its Download and NetworkTopology CSVs
to the trainer (scheduler/announcer/announcer.go:193-259); the reference
trainer dropped them (never implemented). Here the records are columnar numpy
(telemetry.records) and convert straight into the GNN's dense padded
TopoGraph + the PairBatch pool both trainers consume — no CSV unflattening.

Host identity: record host-id strings index into a contiguous node table
(insertion-ordered). Node features are aggregated from the download records
(upload success rate per parent host); probe records supply the edge list and
RTT statistics.

Two construction paths share one vectorized core:

  build_dataset(downloads, probes)   one-shot over full record arrays
  DatasetAccumulator                 incremental — fold announcer chunks in as
                                     they arrive, finalize() in O(nodes+edges
                                     +pairs) at train_close

Both are columnar numpy end-to-end: host ids hashed to 64-bit integers and,
with the packed 64-bit (src,dst) edge keys, interned by one integer-keyed
table (_Interner; first-occurrence order, matching the row-walk's insertion
order, the ids themselves deciding identity), per-(src,dst) probe
aggregation via bincount over the edge rows, neighbor tables via one lexsort
on (src, rtt, arrival) with a vectorized top-max_neighbors cut, and node
features via bincount weights.
The superseded per-row walk survives as _build_dataset_rowloop — the
reference implementation the equivalence tests and the bench A/B pin the
vectorized path against (tests/test_dataset_ingest.py, bench.py
dataset_build).

Threading model: DatasetAccumulator folds run on the trainer's event loop
(about 2.3 ms a 4,096-row announcer chunk at 32,768 hosts on a TPU v5e
machine's host); freeze() takes a cheap consistent snapshot so finalize()
can run on a worker thread while new chunks keep folding.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from dragonfly2_tpu.models.features import FEATURE_DIM, NODE_FEATURE_DIM
from dragonfly2_tpu.models.graphsage import TopoGraph
from dragonfly2_tpu.trainer.synthetic import EDGE_FEATURE_DIM, PairBatch

GIB = float(1 << 30)


@dataclass
class Dataset:
    graph: TopoGraph
    pairs: PairBatch
    host_index: dict[bytes, int]  # host_id -> node row
    # Training-reference feature sketch (ISSUE 15): the per-feature
    # histogram of the pair rows this dataset trains on, frozen HERE at
    # finalize so it describes exactly the distribution the model saw.
    # Ships digest-covered inside the artifact (trainer/artifacts.py) and
    # becomes the serving scheduler's drift baseline. None on the rowloop
    # reference path (kept byte-for-byte r05-shaped for equivalence tests).
    feature_sketch: object | None = None

    @property
    def num_nodes(self) -> int:
        return self.graph.node_feats.shape[0]

    @property
    def num_pairs(self) -> int:
        return len(self.pairs.child)


class _HostTable:
    def __init__(self) -> None:
        self.index: dict[bytes, int] = {}

    def get(self, host_id: bytes) -> int:
        idx = self.index.get(host_id)
        if idx is None:
            idx = self.index[host_id] = len(self.index)
        return idx


@dataclass
class KeyCounts:
    """What an accumulator's get-or-add tables resolved, summed over its
    calls: `looked_up` the positions of a call whose key a table already
    held, `admitted` a call's distinct new keys, given codes, `collisions`
    a call's distinct host or parent ids that took the exact path because
    their hash is another id's."""

    looked_up: int = 0
    admitted: int = 0
    collisions: int = 0


def _sorted_unique(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(sorted unique values, per-element inverse) — np.sort + one searchsorted,
    ~2.5x cheaper than np.unique(return_index/return_inverse) on S-dtype ids
    (measured: the stable argsort unique uses dominates build time)."""
    s = np.sort(ids)
    uniq = s[np.r_[True, s[1:] != s[:-1]]]
    return uniq, np.searchsorted(uniq, ids)


def _unique_first(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sorted distinct keys, each one's first position, per-element inverse)
    for a non-empty integer array — one unstable sort, the first positions
    by a min-reduce over each run of equal keys."""
    order = np.argsort(keys)
    s = keys[order]
    head = np.empty(len(s), bool)
    head[0] = True
    np.not_equal(s[1:], s[:-1], out=head[1:])
    starts = np.flatnonzero(head)
    inv = np.empty(len(keys), np.int64)
    inv[order] = np.cumsum(head) - 1
    return s[starts], np.minimum.reduceat(order, starts), inv


def _in_arrival_order(first: np.ndarray, base: int) -> np.ndarray:
    """Codes base, base + 1, ... for new keys, ranked by first position —
    the numbering a row-by-row walk would give them."""
    codes = np.empty(len(first), np.int64)
    codes[np.argsort(first)] = np.arange(base, base + len(first))
    return codes


def _merge_runs(
    a: tuple[np.ndarray, np.ndarray], b: tuple[np.ndarray, np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """One sorted run of two with no key in common: b's keys go in at their
    search positions, a's fill the rest."""
    (ak, ac), (bk, bc) = a, b
    at = np.searchsorted(ak, bk) + np.arange(len(bk))
    keys = np.empty(len(ak) + len(bk), ak.dtype)
    codes = np.empty(len(keys), np.int64)
    rest = np.ones(len(keys), bool)
    rest[at] = False
    keys[at], codes[at] = bk, bc
    keys[rest], codes[rest] = ak, ac
    return keys, codes


_PHI = np.uint64(0x9E3779B97F4A7C15)


class _Interner:
    """A map from uint64 keys to int64 codes, with get-or-add (`codes`) that
    numbers new keys 0, 1, 2, ... in order of first occurrence across all
    calls — the numbering of a row-by-row walk through _HostTable.

    Every key has a home slot, the top bits of key * φ, in a table kept at
    most a quarter full; a slot holds the code of the first key placed
    there. A key whose home holds another key is a stray, kept in a stack of
    sorted (keys, codes) runs, each more than twice the size of the next. A
    lookup is one probe of each key's home, and a search of the runs for the
    keys whose home holds another key. So a call costs its own keys plus a
    log of the strays for the runs' merges; the table doubles, every key
    placed again, as it passes a quarter full, and no call rebuilds or
    inserts into the whole table.
    """

    __slots__ = ("_bits", "_table", "_keys", "_strays", "n", "counts")

    def __init__(self, counts: KeyCounts):
        self._bits = 10
        self._table = np.full(1 << self._bits, -1, np.int32)
        self._keys = _Grow(np.uint64)  # the key of each code
        self._strays: list[tuple[np.ndarray, np.ndarray]] = []
        self.n = 0  # keys held
        self.counts = counts

    def __len__(self) -> int:
        return self.n

    def _home(self, keys: np.ndarray) -> np.ndarray:
        return (keys * _PHI) >> np.uint64(64 - self._bits)

    def find(self, keys: np.ndarray) -> np.ndarray:
        """Code of each key (any order, repeats allowed), -1 where none."""
        out = self._table[self._home(keys)].astype(np.int64)
        other = np.flatnonzero(out >= 0)
        other = other[self._keys.view()[out[other]] != keys[other]]
        if len(other):
            out[other] = self._find_strays(keys[other])
        return out

    def _find_strays(self, queries: np.ndarray) -> np.ndarray:
        out = np.full(len(queries), -1, np.int64)
        pending = np.arange(len(queries))
        for keys, codes in self._strays:
            q = queries[pending]
            pos = np.searchsorted(keys, q)
            pos[pos == len(keys)] = 0
            hit = keys[pos] == q
            out[pending[hit]] = codes[pos[hit]]
            pending = pending[~hit]
            if not len(pending):
                break
        return out

    def add(self, keys: np.ndarray, codes: np.ndarray) -> None:
        """Hold distinct keys that have no code, under distinct new codes."""
        self._keys.ensure(int(codes.max()) + 1)
        self._keys.view()[codes] = keys
        self.n += len(keys)
        if 4 * self.n > len(self._table):
            held = self._table[self._table >= 0]
            codes = np.concatenate([held, *(c for _, c in self._strays), codes])
            keys = self._keys.view()[codes]
            while 4 * self.n > 1 << self._bits:
                self._bits += 1
            self._table = np.full(1 << self._bits, -1, np.int32)
            self._strays = []
        self._place(keys, codes)

    def _place(self, keys: np.ndarray, codes: np.ndarray) -> None:
        home = self._home(keys)
        free = self._table[home] < 0
        self._table[home[free]] = codes[free]  # keys that share a free home: one write holds it
        stray = np.flatnonzero(self._table[home] != codes)
        if not len(stray):
            return
        stray = stray[np.argsort(keys[stray])]
        runs = self._strays
        runs.append((keys[stray], codes[stray]))
        while len(runs) > 1 and len(runs[-2][0]) <= 2 * len(runs[-1][0]):
            b = runs.pop()
            runs.append(_merge_runs(runs.pop(), b))

    def codes(self, keys: np.ndarray) -> np.ndarray:
        """Get-or-add: int64 code per element, first-occurrence ordered."""
        if not len(keys):
            return np.zeros(0, np.int64)
        out = self.find(keys)
        miss = np.flatnonzero(out < 0)
        self.counts.looked_up += len(keys) - len(miss)
        if len(miss):
            uq, first, inv = _unique_first(keys[miss])
            new = _in_arrival_order(first, self.n)
            self.add(uq, new)
            out[miss] = new[inv]
            self.counts.admitted += len(uq)
        return out


def _words(ids: np.ndarray) -> np.ndarray:
    """S64 ids as [n, 8] uint64 rows (a view where they already are S64)."""
    return np.ascontiguousarray(ids, "S64").view("<u8").reshape(-1, 8)


def _hash_ids(words: np.ndarray) -> np.ndarray:
    """64-bit hash of each id from its eight 8-byte words. Each step is a
    bijection of the running value, so ids that differ in one word never
    share a hash; identity is decided by the id itself (_IdInterner)."""
    h = words[:, 0] * _PHI
    for i in range(1, 8):
        h ^= words[:, i]
        h *= _PHI
    return h ^ (h >> np.uint64(29))


def _same_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per row of two [n, 8] word arrays: equal in every word (the row's
    eight compare flags read as one uint64)."""
    return (a != b).view(np.uint64)[:, 0] == 0


class _IdInterner:
    """Get-or-add from S64 ids (host ids, as the records carry them) to
    contiguous codes in order of first occurrence, through an _Interner over
    the ids' hashes.

    A hash never decides identity. A position whose hash is known is
    compared with the stored id of that hash, one whose hash is new with the
    batch's first id of that hash: two vectorized compares of the ids'
    words. A position that differs (a hash that is another id's, in the
    table or earlier in the batch) takes the exact path: a dict over the ids
    whose hash is another's, with the codes the walk would give them. The
    ids are kept in code order.
    """

    __slots__ = ("_hashes", "_ids", "_spill", "counts")

    def __init__(self, counts: KeyCounts):
        self._hashes = _Interner(counts)  # hash -> the code of the id it is
        self._ids = _Grow(np.uint64, cols=8)
        self._spill: dict[bytes, int] = {}
        self.counts = counts

    def __len__(self) -> int:
        return self._ids.n

    @property
    def ids(self) -> np.ndarray:
        """Every id (S64), at its code (a view: copy to keep)."""
        return self._ids.view().view("S64")[:, 0]

    def find(self, ids: np.ndarray) -> np.ndarray:
        """Code of each id, -1 where it has none; admits nothing."""
        return self._get(_words(ids), admit=False)

    def codes(self, ids: np.ndarray) -> np.ndarray:
        """Get-or-add: int64 code per element, first-occurrence ordered."""
        return self._get(_words(ids), admit=True)

    def _get(self, words: np.ndarray, *, admit: bool) -> np.ndarray:
        h = _hash_ids(words)
        out = self._hashes.find(h)
        known = np.flatnonzero(out >= 0)
        doubt = known[~_same_rows(words.take(known, 0), self._ids.view().take(out[known], 0))]
        miss = np.flatnonzero(out < 0)
        uh, first = np.zeros(0, np.uint64), miss[:0]
        if len(miss):
            uh, first, inv = _unique_first(h[miss])
            first = miss[first]
            same = _same_rows(words.take(miss, 0), words.take(first[inv], 0))
            doubt = np.sort(np.concatenate([doubt, miss[~same]]))
            fresh, group = miss[same], inv[same]
        exact: list[bytes] = []
        if len(doubt):
            ex, at, which = np.unique(words.view("S64")[doubt, 0], return_index=True, return_inverse=True)
            exact, at = ex.tolist(), doubt[at]
            ecodes = np.array([self._spill.get(k, -1) for k in exact], np.int64)
            out[doubt] = ecodes[which]
        if not admit:
            return out
        base = len(self)
        self.counts.looked_up += int((out >= 0).sum())
        self.counts.collisions += len(exact)
        if not len(uh) and not len(exact):
            return out
        enew = np.flatnonzero(ecodes < 0) if len(exact) else np.zeros(0, np.int64)
        firsts = np.concatenate([first, at[enew]]) if len(exact) else first
        new = _in_arrival_order(firsts, base)
        if len(uh):
            self._hashes.add(uh, new[: len(uh)])
            out[fresh] = new[group]
        if len(enew):
            ecodes[enew] = new[len(uh):]
            for k, c in zip([exact[i] for i in enew.tolist()], new[len(uh):].tolist()):
                self._spill[k] = c
            out[doubt] = ecodes[which]
        self._ids.ensure(base + len(new))
        self._ids.view()[new] = words.take(firsts, 0)
        self.counts.admitted += len(new)
        return out


class _Grow:
    """Amortized-doubling growable array (rows on axis 0)."""

    __slots__ = ("a", "n")

    def __init__(self, dtype, cols: int | None = None):
        shape = (0,) if cols is None else (0, cols)
        self.a = np.zeros(shape, dtype)
        self.n = 0

    def ensure(self, rows: int) -> None:
        """Grow (zero-filled) so that `rows` total rows are addressable."""
        if rows > len(self.a):
            cap = max(rows, 2 * len(self.a), 256)
            grown = np.zeros((cap,) + self.a.shape[1:], self.a.dtype)
            grown[: self.n] = self.a[: self.n]
            self.a = grown
        self.n = max(self.n, rows)

    def view(self) -> np.ndarray:
        return self.a[: self.n]


def _interleave(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[a0, b0, a1, b1, ...] — the id sequence a per-row (a, b) walk interns."""
    out = np.empty(2 * len(a), dtype=a.dtype)
    out[0::2] = a
    out[1::2] = b
    return out


class FrozenIngest:
    """Immutable snapshot of accumulator state; finalize() is pure and safe
    to run on a worker thread while the live accumulator keeps folding."""

    def __init__(
        self,
        host_ids: np.ndarray,
        edge_src: np.ndarray,
        edge_dst: np.ndarray,
        edge_sum: np.ndarray,
        edge_cnt: np.ndarray,
        stat_rows: np.ndarray,
        stat_tot: np.ndarray,
        stat_succ: np.ndarray,
        stat_bw: np.ndarray,
        pair_chunks: tuple[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray], ...],
    ):
        self._host_ids = host_ids
        self._edge_src = edge_src
        self._edge_dst = edge_dst
        self._edge_sum = edge_sum
        self._edge_cnt = edge_cnt
        self._stat_rows = stat_rows
        self._stat_tot = stat_tot
        self._stat_succ = stat_succ
        self._stat_bw = stat_bw
        self._pair_chunks = pair_chunks

    def finalize(self, *, max_neighbors: int = 16, min_nodes: int = 8) -> Dataset:
        n = max(len(self._host_ids), min_nodes)
        k = max_neighbors
        neighbors = np.zeros((n, k), np.int32)
        mask = np.zeros((n, k), np.float32)
        edge_feats = np.zeros((n, k, EDGE_FEATURE_DIM), np.float32)

        m = len(self._edge_src)
        if m:
            agg = self._edge_sum / self._edge_cnt[:, None]
            src = self._edge_src
            # stable (src, rtt_mean, arrival) order == the row-walk's
            # per-source stable sort by RTT with insertion-order tie-break;
            # keep the lowest-RTT max_neighbors per source (they matter most)
            order = np.lexsort((np.arange(m), agg[:, 0], src))
            s_sorted = src[order]
            starts = np.flatnonzero(np.r_[True, s_sorted[1:] != s_sorted[:-1]])
            seg_len = np.diff(np.r_[starts, m])
            pos_in_src = np.arange(m) - np.repeat(starts, seg_len)
            keep = pos_in_src < k
            sel = order[keep]
            rows = s_sorted[keep]
            cols = pos_in_src[keep]
            a = agg[sel]
            neighbors[rows, cols] = self._edge_dst[sel].astype(np.int32)
            mask[rows, cols] = 1.0
            edge_feats[rows, cols, 0] = a[:, 0] / 100.0  # ms -> per-100ms
            edge_feats[rows, cols, 1] = a[:, 1] / 100.0
            edge_feats[rows, cols, 2] = a[:, 2] / 100.0
            edge_feats[rows, cols, 3] = np.minimum(1.0, a[:, 3] / 30.0)

        # --- node features aggregated from download history ---
        node_feats = np.zeros((n, NODE_FEATURE_DIM), np.float32)
        if len(self._stat_rows):
            main = self._stat_rows
            present = main >= 0  # parents only ever seen in failed rows w/o probes drop out
            rows = main[present]
            total_cnt = np.zeros(n)
            success_cnt = np.zeros(n)
            bw_sum = np.zeros(n)
            total_cnt[rows] = self._stat_tot[present]
            success_cnt[rows] = self._stat_succ[present]
            bw_sum[rows] = self._stat_bw[present]
            served = total_cnt > 0
            node_feats[served, 1] = success_cnt[served] / total_cnt[served]
            node_feats[served, 5] = bw_sum[served] / total_cnt[served]
        # pair features carry the rest of the observable signal; idc/location
        # hash slots stay zero until host announces flow into telemetry

        if self._pair_chunks:
            cols4 = list(zip(*self._pair_chunks))
            pairs = PairBatch(
                np.concatenate(cols4[0]),
                np.concatenate(cols4[1]),
                np.concatenate(cols4[2]),
                np.concatenate(cols4[3]),
            )
        else:
            pairs = PairBatch(
                np.asarray([0], np.int32),
                np.asarray([0], np.int32),
                np.zeros((1, FEATURE_DIM), np.float32),
                np.asarray([0.0], np.float32),
            )
        graph = TopoGraph(node_feats, neighbors, mask, edge_feats)
        # freeze the training-reference sketch from the pair rows the model
        # will actually fit (ISSUE 15); one vectorized pass, O(pairs x F)
        from dragonfly2_tpu.models.features import FEATURE_NAMES
        from dragonfly2_tpu.observability.sketches import FeatureSketch

        sketch = FeatureSketch(FEATURE_DIM, names=FEATURE_NAMES)
        sketch.update(pairs.feats)
        host_index = dict(zip(self._host_ids.tolist(), range(len(self._host_ids))))
        return Dataset(graph=graph, pairs=pairs, host_index=host_index, feature_sketch=sketch)


class DatasetAccumulator:
    """Incremental telemetry→dataset ingest.

    Fold each announcer chunk in as it arrives (add_downloads/add_probes);
    finalize() materializes the Dataset from the aggregated state in
    O(nodes + edges + retained pairs) — no re-walk of raw telemetry rows, no
    retained raw record arrays. State kept:

      - host table        id -> node row, first-occurrence ordered
      - pair pool         columnar (child, parent, feats, label) chunks; when
                          max_pair_rows > 0, oldest whole chunks are evicted
                          once the newer ones alone reach the cap (the rolling
                          pool the per-upload row arrays used to provide, at
                          ~76 B/pair instead of ~376 B/raw row)
      - edge stats        per-(src,dst) float64 stat sums + probe-row counts,
                          rows keyed by packed 64-bit (src<<32|dst), in
                          first-occurrence order
      - node counters     per-parent-id totals/successes/bandwidth sums, in a
                          side table so a parent first seen in a failed row
                          still counts once (and only once) it enters the host
                          table via a later ok-row or probe — matching the
                          one-shot walk, which counts after full interning

    Fold order defines node numbering: per upload the announcer streams all
    download chunks then all probe chunks, which reproduces build_dataset's
    interning order exactly (the chunked≡one-shot equivalence tests pin this).
    The three get-or-add tables (hosts, edges, parent ids) are _Interner
    tables over 64-bit keys; `keys` counts what they resolved.
    """

    def __init__(self, *, max_pair_rows: int = 0):
        self.keys = KeyCounts()
        self.hosts = _IdInterner(self.keys)
        self.max_pair_rows = max_pair_rows
        self._pair_chunks: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = []
        self.pair_rows = 0
        self._edges = _Interner(self.keys)
        self._edge_src = _Grow(np.int64)
        self._edge_dst = _Grow(np.int64)
        self._edge_sum = _Grow(np.float64, cols=EDGE_FEATURE_DIM)
        self._edge_cnt = _Grow(np.int64)
        self._stats = _IdInterner(self.keys)
        self._stat_tot = _Grow(np.float64)
        self._stat_succ = _Grow(np.float64)
        self._stat_bw = _Grow(np.float64)
        self.download_rows = 0
        self.probe_rows = 0

    @property
    def num_hosts(self) -> int:
        return len(self.hosts)

    @property
    def num_edges(self) -> int:
        return self._edge_src.n

    def add_downloads(self, arr: np.ndarray) -> int:
        """Fold one DOWNLOAD_DTYPE chunk; returns rows folded."""
        rows = len(arr)
        if rows == 0:
            return 0
        self.download_rows += rows

        # field-wise extraction: indexing a single column copies only that
        # column; fancy-indexing the structured array would copy every field
        success = arr["success"]
        parent = arr["parent_host_id"]
        has_parent = parent != b""
        ok = success & has_parent  # back-to-source trains nothing pairwise
        if ok.any():
            ids = _interleave(arr["child_host_id"][ok], parent[ok])
            codes = self.hosts.codes(ids)
            labels = np.minimum(
                1.0, arr["bandwidth_bps"][ok].astype(np.float64) / GIB
            ).astype(np.float32)
            self._pair_chunks.append(
                (
                    codes[0::2].astype(np.int32),
                    codes[1::2].astype(np.int32),
                    arr["pair_features"][ok].astype(np.float32),
                    labels,
                )
            )
            self.pair_rows += int(ok.sum())
            self._evict_pairs()

        # --- per-parent upload counters (all rows, success or not) ---
        if has_parent.any():
            codes = self._stats.codes(parent[has_parent])
            nstat = len(self._stats)
            for g in (self._stat_tot, self._stat_succ, self._stat_bw):
                g.ensure(nstat)
            self._stat_tot.view()[:] += np.bincount(codes, minlength=nstat)
            su = success[has_parent]
            if su.any():
                okc = codes[su]
                self._stat_succ.view()[:] += np.bincount(okc, minlength=nstat)
                bw = np.minimum(
                    1.0, arr["bandwidth_bps"][has_parent][su].astype(np.float64) / GIB
                )
                self._stat_bw.view()[:] += np.bincount(
                    okc, weights=bw, minlength=nstat
                )
        return rows

    def _edge_rows(self, s: np.ndarray, d: np.ndarray) -> np.ndarray:
        """Get-or-add edge-table rows for (src, dst) node rows given in
        arrival order (duplicates allowed); new edges are appended in
        first-occurrence order. Returns the edge row per position."""
        base = len(self._edges)
        rows = self._edges.codes(((s << 32) | d).view(np.uint64))
        total = len(self._edges)
        if total > base:
            for g in (self._edge_src, self._edge_dst, self._edge_sum, self._edge_cnt):
                g.ensure(total)
            fresh = rows >= base
            self._edge_src.view()[rows[fresh]] = s[fresh]
            self._edge_dst.view()[rows[fresh]] = d[fresh]
        return rows

    def add_probes(self, arr: np.ndarray) -> int:
        """Fold one PROBE_DTYPE chunk; returns rows folded."""
        rows = len(arr)
        if rows == 0:
            return 0
        self.probe_rows += rows

        ids = _interleave(arr["src_host_id"], arr["dst_host_id"])
        codes = self.hosts.codes(ids)
        erows = self._edge_rows(codes[0::2], codes[1::2])
        uniq_rows, inv = _sorted_unique(erows)

        stats = np.empty((rows, EDGE_FEATURE_DIM), np.float64)
        stats[:, 0] = arr["rtt_mean_ms"]
        stats[:, 1] = arr["rtt_std_ms"]
        stats[:, 2] = arr["rtt_min_ms"]
        stats[:, 3] = arr["probe_count"]
        esum = self._edge_sum.view()
        for c in range(EDGE_FEATURE_DIM):
            esum[uniq_rows, c] += np.bincount(
                inv, weights=stats[:, c], minlength=len(uniq_rows)
            )
        self._edge_cnt.view()[uniq_rows] += np.bincount(inv, minlength=len(uniq_rows))
        return rows

    def merge_from(self, other: "DatasetAccumulator") -> None:
        """Fold another accumulator's aggregated state in — O(other's
        nodes + edges + pair chunks), never touching raw rows. The service
        commits a session's accumulator into the shared pool at train_close
        this way: a session that dies mid-upload (RPC failure, TTL eviction)
        contributes NOTHING, so an announcer retry of the same snapshot can
        never double-count. Host/edge arrival order follows other's internal
        first-occurrence order, exactly as if its rows had been folded here
        directly."""
        if other.download_rows == 0 and other.probe_rows == 0:
            return
        # hosts: other's code i holds the id at position i of its id array;
        # get-or-add yields the remap other-code -> self-code
        remap = self.hosts.codes(other.hosts.ids)

        for child, parent, feats, labels in other._pair_chunks:
            self._pair_chunks.append(
                (
                    remap[child].astype(np.int32),
                    remap[parent].astype(np.int32),
                    feats,
                    labels,
                )
            )
            self.pair_rows += len(child)
        self._evict_pairs()

        m = other._edge_src.n
        if m:
            s = remap[other._edge_src.view()]
            d = remap[other._edge_dst.view()]
            erows = self._edge_rows(s, d)  # other's edges are unique keys
            self._edge_sum.view()[erows] += other._edge_sum.view()
            self._edge_cnt.view()[erows] += other._edge_cnt.view()

        if len(other._stats):
            scodes = self._stats.codes(other._stats.ids)
            nstat = len(self._stats)
            for g in (self._stat_tot, self._stat_succ, self._stat_bw):
                g.ensure(nstat)
            self._stat_tot.view()[scodes] += other._stat_tot.view()
            self._stat_succ.view()[scodes] += other._stat_succ.view()
            self._stat_bw.view()[scodes] += other._stat_bw.view()

        self.download_rows += other.download_rows
        self.probe_rows += other.probe_rows

    def _evict_pairs(self) -> None:
        """Rolling-pool semantics of the old per-session row arrays: evict
        oldest whole chunks while the remainder alone still covers the cap."""
        cap = self.max_pair_rows
        if cap <= 0:
            return
        chunks = self._pair_chunks
        while len(chunks) > 1 and self.pair_rows - len(chunks[0][0]) >= cap:
            self.pair_rows -= len(chunks.pop(0)[0])

    def freeze(self) -> FrozenIngest:
        """Cheap consistent snapshot (copies only the aggregate arrays; pair
        chunks are append-only so a shallow tuple copy suffices: no array of a
        chunk is written after it is appended, and eviction pops the live
        list, not the tuple). Merges and folds that land after it, on the
        loop while finalize() runs on a worker thread, leave it as it was."""
        return FrozenIngest(
            host_ids=self.hosts.ids.copy(),
            edge_src=self._edge_src.view().copy(),
            edge_dst=self._edge_dst.view().copy(),
            edge_sum=self._edge_sum.view().copy(),
            edge_cnt=self._edge_cnt.view().copy(),
            stat_rows=self.hosts.find(self._stats.ids),
            stat_tot=self._stat_tot.view().copy(),
            stat_succ=self._stat_succ.view().copy(),
            stat_bw=self._stat_bw.view().copy(),
            pair_chunks=tuple(self._pair_chunks),
        )

    def finalize(self, *, max_neighbors: int = 16, min_nodes: int = 8) -> Dataset:
        """Materialize the Dataset from aggregated state (non-destructive —
        keep folding and finalize again later)."""
        return self.freeze().finalize(max_neighbors=max_neighbors, min_nodes=min_nodes)


def build_dataset(
    downloads: np.ndarray,
    probes: np.ndarray,
    *,
    max_neighbors: int = 16,
    min_nodes: int = 8,
) -> Dataset:
    """downloads: DOWNLOAD_DTYPE rows; probes: PROBE_DTYPE rows.

    One-shot wrapper over the vectorized accumulator; equivalent to the
    per-row reference walk (_build_dataset_rowloop) up to float32-vs-float64
    accumulation order in the edge statistics.
    """
    acc = DatasetAccumulator()
    if len(downloads):  # 0-row placeholders may be plain (non-structured) zeros
        acc.add_downloads(downloads)
    if len(probes):
        acc.add_probes(probes)
    return acc.finalize(max_neighbors=max_neighbors, min_nodes=min_nodes)


def _build_dataset_rowloop(
    downloads: np.ndarray,
    probes: np.ndarray,
    *,
    max_neighbors: int = 16,
    min_nodes: int = 8,
) -> Dataset:
    """Reference implementation: the superseded per-row Python walk.

    Kept verbatim for the equivalence suite (tests/test_dataset_ingest.py)
    and the bench A/B (bench.py dataset_build) — every behavior of
    build_dataset is defined as "what this does", so changes must land here
    AND in the vectorized path together.
    """
    hosts = _HostTable()

    # --- pairs from download records (child <- parent transfers) ---
    child_idx, parent_idx, feats, labels = [], [], [], []
    ok = downloads[downloads["success"]] if len(downloads) else downloads
    for row in ok:
        if not row["parent_host_id"]:
            continue  # back-to-source rows train nothing pairwise
        c = hosts.get(bytes(row["child_host_id"]))
        p = hosts.get(bytes(row["parent_host_id"]))
        child_idx.append(c)
        parent_idx.append(p)
        feats.append(np.asarray(row["pair_features"], np.float32))  # dflint: disable=DF033 rowloop reference for the vectorized path
        labels.append(min(1.0, float(row["bandwidth_bps"]) / GIB))

    # --- edges from probe records, aggregated per (src, dst) ---
    edge_stats: dict[tuple[int, int], list[np.ndarray]] = {}
    for row in probes:
        s = hosts.get(bytes(row["src_host_id"]))
        d = hosts.get(bytes(row["dst_host_id"]))
        edge_stats.setdefault((s, d), []).append(
            np.array(  # dflint: disable=DF033 rowloop reference for the vectorized path
                [row["rtt_mean_ms"], row["rtt_std_ms"], row["rtt_min_ms"], row["probe_count"]],
                np.float32,
            )
        )

    n = max(len(hosts.index), min_nodes)
    neighbors = np.zeros((n, max_neighbors), np.int32)
    mask = np.zeros((n, max_neighbors), np.float32)
    edge_feats = np.zeros((n, max_neighbors, EDGE_FEATURE_DIM), np.float32)
    per_src: dict[int, list[tuple[int, np.ndarray]]] = {}
    for (s, d), stats in edge_stats.items():
        agg = np.mean(np.stack(stats), axis=0)  # dflint: disable=DF033 rowloop reference for the vectorized path
        per_src.setdefault(s, []).append((d, agg))
    for s, dests in per_src.items():
        # keep the lowest-RTT neighbors when over-degree (they matter most)
        dests.sort(key=lambda t: t[1][0])
        for k, (d, agg) in enumerate(dests[:max_neighbors]):
            neighbors[s, k] = d
            mask[s, k] = 1.0
            edge_feats[s, k, 0] = agg[0] / 100.0  # ms -> per-100ms
            edge_feats[s, k, 1] = agg[1] / 100.0
            edge_feats[s, k, 2] = agg[2] / 100.0
            edge_feats[s, k, 3] = min(1.0, agg[3] / 30.0)

    # --- node features aggregated from download history ---
    node_feats = np.zeros((n, NODE_FEATURE_DIM), np.float32)
    success_cnt = np.zeros(n)
    total_cnt = np.zeros(n)
    bw_sum = np.zeros(n)
    for row in downloads:
        if not row["parent_host_id"]:
            continue
        p = hosts.index.get(bytes(row["parent_host_id"]))
        if p is None:
            continue
        total_cnt[p] += 1
        if row["success"]:
            success_cnt[p] += 1
            bw_sum[p] += min(1.0, float(row["bandwidth_bps"]) / GIB)
    served = total_cnt > 0
    node_feats[served, 1] = success_cnt[served] / total_cnt[served]  # upload_success_rate
    node_feats[served, 5] = bw_sum[served] / total_cnt[served]  # network_tx_norm proxy
    # pair features carry the rest of the observable signal; idc/location hash
    # slots stay zero until host announces flow into telemetry (future work)

    pairs = PairBatch(
        np.asarray(child_idx or [0], np.int32),
        np.asarray(parent_idx or [0], np.int32),
        (np.stack(feats) if feats else np.zeros((1, FEATURE_DIM), np.float32)),
        np.asarray(labels or [0.0], np.float32),
    )
    graph = TopoGraph(node_feats, neighbors, mask, edge_feats)
    return Dataset(graph=graph, pairs=pairs, host_index=dict(hosts.index))


def split_pairs(pairs: PairBatch, holdout: float = 0.1, seed: int = 0) -> tuple[PairBatch, PairBatch]:
    """Random train/eval split of the pair pool."""
    n = len(pairs.child)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    n_eval = max(1, int(n * holdout)) if n > 1 else 0
    ev, tr = perm[:n_eval], perm[n_eval:]
    take = lambda idx: PairBatch(*(np.asarray(a)[idx] for a in pairs))
    return take(tr if len(tr) else perm), take(ev if len(ev) else perm)
