"""Plain reference of one dereplication job, independent of galah_tpu.

It follows the semantics Galah states (reference: Galah v0.4.2
src/clusterer.rs, src/skani.rs, src/finch.rs) and that this repository
implements, written from that description and the MurmurHash3 spec:

  quality    CheckM2 completeness / contamination, the min-completeness
             and max-contamination filters, then the Parks2020_reduced
             score (comp*100 - 5*cont*100 - 5*contigs/100 -
             5*ambiguous/1e5) descending, ties in input order.
  finch      canonical 21-mers (lexicographic minimum of the k-mer and
             its reverse complement, never spanning a contig end),
             MurmurHash3_x64_128 h1 (seed 0) of the k-mer's ASCII bytes,
             the 1000 smallest distinct hashes; Jaccard over the
             smallest `size` distinct hashes of the union; ANI =
             1 + ln(2j/(1+j))/k; pairs with ANI >= the threshold.
  skani      markers: distinct canonical 15-mer hashes below
             2^64/1000; pairs i<j with marker containment |A&B| /
             min(|A|,|B|) >= 0.8^15 are screened; each screened pair
             gets exact fragment ANI, kept where >= the threshold.
  subsample  FracMinHash compression s of fragment ANI (the job's
             `--ani-subsample`, 1 by default): a canonical 15-mer takes
             part only where its hash is below 2^64 // s, both in the
             reference's distinct set and among the query positions.
             The markers, below 2^64/1000, are a subset of any sample
             with s <= 1000, so the screen does not change.
  fragment   the query is cut into fragments of `fraglen` positions; a
  ANI        position holds a sampled 15-mer that lies inside its
             fragment and its contig. Per fragment: total positions and
             matched ones (k-mer in the reference's distinct set). A
             fragment counts if total >= half of fraglen-k+1 divided
             by the compression (and >= 1); it aligns if matched/total
             >= 0.8^k. The collision rate r is the mean matched
             fraction of counted, unaligned fragments; identity =
             clip((c - r) / (1 - r))^(1/k) for a fragment's matched
             fraction c; directed ANI = mean identity over aligned
             fragments, aligned fraction = aligned / counted. A pair
             passes if either direction's aligned fraction reaches
             min_aligned_fraction (and something aligned); its ANI is
             the larger direction. skani as a clusterer reads a failed
             pair as 0.
  greedy     preclusters are the connected components of the precluster
             pairs, biggest first (ties: lowest member first); in each,
             genomes in quality order become representatives unless a
             representative with a precluster pair has ANI >= the
             threshold; every other genome joins the representative of
             highest ANI among those it has a precluster pair with (ties
             to the lower one). Output rows: representative, member;
             each cluster's representative first.

Membership is decided on 2-bit k-mer codes rather than their hashes, so
the result differs from a hash-set test only where two distinct 15-mers
share a 64-bit hash. The float arithmetic of ANI is float64; `dtype`
switches it to float32 for the control.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SKANI_K = 15
FINCH_K = 21
SKETCH_SIZE = 1000
MARKER_C = 1000
SCREEN_IDENTITY = 0.80
IDENTITY_FLOOR = 0.80
MIN_WINDOW_VALID_FRAC = 0.5
PRECLUSTER_METHODS = ("skani", "finch")
CLUSTER_METHODS = ("skani",)

_ASCII = np.frombuffer(b"ACGT", dtype=np.uint8)
_C1 = np.uint64(0x87C37B91114253D5)
_C2 = np.uint64(0x4CF5AD432745937F)


# ---------------------------------------------------------------- hashing

def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _fmix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> np.uint64(33))


def murmur3_h1(words: Sequence[np.ndarray], length: int) -> np.ndarray:
    """MurmurHash3_x64_128 (seed 0), first half, of keys of `length`
    bytes given as little-endian u64 words (bytes past `length` zero)."""
    n = words[0].shape[0]
    h1 = np.zeros(n, dtype=np.uint64)
    h2 = np.zeros(n, dtype=np.uint64)
    nblocks = length // 16
    for b in range(nblocks):
        k1 = words[2 * b] * _C1
        k1 = _rotl(k1, 31) * _C2
        h1 = h1 ^ k1
        h1 = _rotl(h1, 27) + h2
        h1 = h1 * np.uint64(5) + np.uint64(0x52DCE729)
        k2 = words[2 * b + 1] * _C2
        k2 = _rotl(k2, 33) * _C1
        h2 = h2 ^ k2
        h2 = _rotl(h2, 31) + h1
        h2 = h2 * np.uint64(5) + np.uint64(0x38495AB5)
    rem = length & 15
    if rem > 8:
        k2 = words[2 * nblocks + 1] * _C2
        k2 = _rotl(k2, 33) * _C1
        h2 = h2 ^ k2
    if rem > 0:
        k1 = words[2 * nblocks] * _C1
        k1 = _rotl(k1, 31) * _C2
        h1 = h1 ^ k1
    h1 = h1 ^ np.uint64(length)
    h2 = h2 ^ np.uint64(length)
    h1 = h1 + h2
    h2 = h2 + h1
    h1 = _fmix(h1)
    h2 = _fmix(h2)
    return h1 + h2


def _byte_lut() -> np.ndarray:
    """Eight 2-bit base codes (first base in the top bits) -> their ASCII
    bytes as a little-endian u64 (first base in the lowest byte)."""
    x = np.arange(1 << 16, dtype=np.uint64)
    out = np.zeros(1 << 16, dtype=np.uint64)
    for b in range(8):
        code = (x >> np.uint64(2 * (7 - b))) & np.uint64(3)
        out |= _ASCII.astype(np.uint64)[code] << np.uint64(8 * b)
    return out


_LUT = _byte_lut()


def ascii_words(ids: np.ndarray, k: int) -> List[np.ndarray]:
    """Little-endian u64 words of the ASCII bytes of k-mers given as
    2-bit codes (first base most significant), bytes past k zero."""
    ids = ids.astype(np.uint64)
    words = []
    for w in range(-(-k // 8)):
        nb = min(8, k - 8 * w)          # bases in this word
        shift = 2 * (k - 8 * w - nb)    # low bits below this word
        chunk = (ids >> np.uint64(shift)) & np.uint64((1 << (2 * nb)) - 1)
        word = _LUT[chunk << np.uint64(2 * (8 - nb))]
        if nb < 8:
            word &= np.uint64((1 << (8 * nb)) - 1)
        words.append(word)
    return words


def _reverse_complement(fwd: np.ndarray, k: int) -> np.ndarray:
    """2-bit codes of the reverse complements (codes A0 C1 G2 T3)."""
    bits = 8 * fwd.dtype.itemsize
    m2 = fwd.dtype.type(int("33" * (bits // 8), 16))
    m4 = fwd.dtype.type(int("0F" * (bits // 8), 16))
    x = ~fwd
    x = ((x >> fwd.dtype.type(2)) & m2) | ((x & m2) << fwd.dtype.type(2))
    x = ((x >> fwd.dtype.type(4)) & m4) | ((x & m4) << fwd.dtype.type(4))
    x = x.byteswap()
    return x >> fwd.dtype.type(bits - 2 * k)


# ---------------------------------------------------------------- k-mers

class KmerView:
    """Per-genome k-mer positions for one k: the canonical 2-bit code of
    each start position of the concatenated contigs (lexicographic
    minimum of the k-mer and its reverse complement), and whether it
    lies inside one contig."""

    def __init__(self, contigs: Sequence[np.ndarray], k: int) -> None:
        codes = np.concatenate(contigs)
        n = codes.shape[0]
        m = max(n - k + 1, 0)
        self.k = k
        self.n_pos = m
        valid = np.ones(m, dtype=bool)
        start = 0
        for c in contigs[:-1]:
            start += c.shape[0]
            valid[max(start - k + 1, 0):start] = False
        for p in np.flatnonzero(codes > 3):
            valid[max(p - k + 1, 0):p + 1] = False
        dtype = np.uint64 if 2 * k > 32 else np.uint32
        c = np.minimum(codes, 3).astype(dtype)
        fwd = np.zeros(m, dtype=dtype)
        two = dtype(2)
        for i in range(k):
            fwd <<= two
            fwd |= c[i:i + m]
        self.canon = np.minimum(fwd, _reverse_complement(fwd, k))
        self.valid = valid

    def hashes(self, ids: Optional[np.ndarray] = None) -> np.ndarray:
        """MurmurHash3 h1 of the ASCII bytes of canonical k-mers (all
        positions, or the given codes)."""
        ids = self.canon if ids is None else ids
        return murmur3_h1(ascii_words(ids, self.k), self.k)


def ani_subsample(config: dict) -> int:
    """The FracMinHash compression c of a configuration's exact
    fragment ANI: what the job gets as `--ani-subsample` and the
    reference applies; 1, every k-mer, where it is not given."""
    return config.get("widths", {}).get("ani_subsample", 1)


class GenomeData:
    """What the reference needs of one genome; its fragment-ANI k-mers
    sampled at compression `subsample`."""

    def __init__(self, name: str, contigs: Sequence[np.ndarray],
                 need_finch: bool, need_skani: bool,
                 fraglen: int, subsample: int = 1) -> None:
        self.name = name
        if need_finch:
            view = KmerView(contigs, FINCH_K)
            h = view.hashes(view.canon[view.valid])
            cand = h if h.shape[0] <= 4 * SKETCH_SIZE else np.partition(
                h, 4 * SKETCH_SIZE)[:4 * SKETCH_SIZE]
            sketch = np.unique(cand)
            if sketch.shape[0] < SKETCH_SIZE and h.shape[0] > cand.shape[0]:
                sketch = np.unique(h)
            self.sketch = sketch[:SKETCH_SIZE]
        if need_skani:
            view = KmerView(contigs, SKANI_K)
            distinct = np.unique(view.canon[view.valid])
            h = view.hashes(distinct)
            self.markers = np.sort(h[h < np.uint64((1 << 64) // MARKER_C)])
            sampled = view.valid
            if subsample > 1:
                keep = h < np.uint64((1 << 64) // subsample)
                sampled = view.valid.copy()
                sampled[sampled] = keep[np.searchsorted(
                    distinct, view.canon[sampled])]
                distinct = distinct[keep]
            self.distinct = distinct.astype(np.uint32)
            self.n_windows = -(-view.n_pos // fraglen)
            # query positions: sampled k-mer inside its fragment and its
            # contig
            pos = np.flatnonzero(
                sampled
                & (np.arange(view.n_pos) % fraglen < fraglen - SKANI_K + 1))
            self.qids = view.canon[pos].astype(np.uint32)
            win = pos // fraglen
            self.totals = np.bincount(win, minlength=self.n_windows)
            # fragments holding query positions, and where each starts
            self.qwins = np.flatnonzero(self.totals)
            self.qstarts = np.searchsorted(win, self.qwins)


def build_genomes(genomes: Sequence[Tuple[str, Sequence[np.ndarray]]],
                  need_finch: bool, need_skani: bool, fraglen: int,
                  threads: int, subsample: int = 1
                  ) -> Dict[str, GenomeData]:
    def one(item):
        name, contigs = item
        return GenomeData(name, contigs, need_finch, need_skani, fraglen,
                          subsample)

    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        return {g.name: g for g in pool.map(one, genomes)}


# ---------------------------------------------------------------- finch

def mash_counts(a: np.ndarray, b: np.ndarray,
                size: int = SKETCH_SIZE) -> Tuple[int, int]:
    """(shared, seen) over the smallest `size` distinct hashes of the
    union of two sorted sketches."""
    union = np.union1d(a, b)
    total = min(size, union.shape[0])
    if total == 0:
        return 0, 0
    top = union[total - 1]
    shared = np.intersect1d(a, b, assume_unique=True)
    return int((shared <= top).sum()), total


def mash_ani(common: int, total: int, k: int = FINCH_K,
             dtype=np.float64) -> float:
    if common <= 0:
        return 0.0
    one = dtype(1.0)
    j = dtype(common) / dtype(max(total, 1))
    d = -np.log(dtype(2.0) * j / (one + j)) / dtype(k)
    return float(one - d)


def finch_pairs(names: Sequence[str], data: Dict[str, GenomeData],
                min_ani: float, dtype=np.float64) -> Dict[Tuple[int, int],
                                                          float]:
    out = {}
    for i in range(len(names)):
        a = data[names[i]].sketch
        for j in range(i + 1, len(names)):
            common, total = mash_counts(a, data[names[j]].sketch)
            ani = mash_ani(common, total, dtype=dtype)
            if ani >= min_ani:
                out[(i, j)] = ani
    return out


# ---------------------------------------------------------------- skani

def screen_pairs(names: Sequence[str],
                 data: Dict[str, GenomeData]) -> List[Tuple[int, int]]:
    """i<j pairs whose marker containment reaches 0.8^15."""
    c_floor = SCREEN_IDENTITY ** SKANI_K
    owner = np.concatenate([np.full(data[n].markers.shape[0], i)
                            for i, n in enumerate(names)])
    hashes = np.concatenate([data[n].markers for n in names])
    order = np.argsort(hashes, kind="stable")
    hashes, owner = hashes[order], owner[order]
    counts = np.array([data[n].markers.shape[0] for n in names])
    inter: Dict[Tuple[int, int], int] = {}
    bounds = np.flatnonzero(np.diff(hashes)) + 1
    for group in np.split(owner, bounds):
        if group.shape[0] < 2:
            continue
        g = np.sort(group)
        for x in range(g.shape[0]):
            for y in range(x + 1, g.shape[0]):
                key = (int(g[x]), int(g[y]))
                inter[key] = inter.get(key, 0) + 1
    out = []
    for (i, j), c in sorted(inter.items()):
        denom = float(min(counts[i], counts[j]))
        if denom > 0 and float(c) >= c_floor * denom:
            out.append((i, j))
    return out


class Directed:
    """Per-fragment matched counts of one query against one
    reference."""

    __slots__ = ("matched", "totals")

    def __init__(self, matched: np.ndarray, totals: np.ndarray) -> None:
        self.matched = matched
        self.totals = totals


def directed_ani(d: Directed, fraglen: int, dtype=np.float64,
                 subsample: int = 1) -> Tuple[float, int, int]:
    """(ani, aligned fragments, counted fragments) of counts taken at
    compression `subsample`."""
    k = SKANI_K
    matched = d.matched.astype(dtype)
    total = d.totals.astype(dtype)
    min_valid = MIN_WINDOW_VALID_FRAC * (fraglen - k + 1) / subsample
    ok = total >= max(min_valid, 1.0)
    c = np.where(ok, matched / np.maximum(total, dtype(1.0)), dtype(0.0))
    aligned = ok & (c >= dtype(IDENTITY_FLOOR ** k))
    fm, ft = int(aligned.sum()), int(ok.sum())
    if fm == 0:
        return 0.0, 0, ft
    below = ok & ~aligned
    nb = int(below.sum())
    r = c[below].sum(dtype=dtype) / dtype(nb) if nb else dtype(0.0)
    adj = np.clip((c[aligned] - r) / max(dtype(1.0) - r, dtype(1e-9)),
                  dtype(1e-12), dtype(1.0))
    identity = adj ** (dtype(1.0) / dtype(k))
    return float(identity.sum(dtype=dtype) / dtype(fm)), fm, ft


def combine(ab: Tuple[float, int, int], ba: Tuple[float, int, int],
            min_af: float) -> Optional[float]:
    gate = ((ab[2] > 0 and ab[1] / max(ab[2], 1) >= min_af)
            or (ba[2] > 0 and ba[1] / max(ba[2], 1) >= min_af))
    if not gate or (ab[1] == 0 and ba[1] == 0):
        return None
    return max(ab[0], ba[0])


class MembershipTable:
    """Direct-address bit table over every 2-bit 15-mer code: bit b of
    entry x is set when reference b holds k-mer x. Eight references at
    most; fills and queries run on `pool`'s threads (fills split the
    table into disjoint code ranges)."""

    BITS = 8
    _SPLIT = 8

    def __init__(self, pool: ThreadPoolExecutor) -> None:
        self.table = np.zeros(1 << (2 * SKANI_K), dtype=np.uint8)
        self.refs: List[GenomeData] = []
        self.pool = pool

    def _apply(self, g: GenomeData, bit: int, set_: bool) -> None:
        edges = np.searchsorted(
            g.distinct, np.linspace(0, 1 << (2 * SKANI_K), self._SPLIT + 1))

        def part(t):
            ids = g.distinct[edges[t]:edges[t + 1]]
            if set_:
                self.table[ids] |= np.uint8(1 << bit)
            else:
                self.table[ids] = 0

        list(self.pool.map(part, range(self._SPLIT)))

    def add(self, g: GenomeData) -> None:
        if len(self.refs) >= self.BITS:
            raise ValueError("membership table is full")
        self._apply(g, len(self.refs), True)
        self.refs.append(g)

    @property
    def full(self) -> bool:
        return len(self.refs) >= self.BITS

    def clear(self) -> None:
        for g in self.refs:
            self._apply(g, 0, False)
        self.refs = []

    def query(self, q: GenomeData) -> Dict[str, Directed]:
        """Directed counts of q against every reference held."""
        bits = self.table[q.qids]
        unpacked = np.unpackbits(bits[:, None], axis=1,
                                 count=len(self.refs), bitorder="little")
        sums = np.add.reduceat(unpacked, q.qstarts, axis=0, dtype=np.int64)
        out = {}
        for b, g in enumerate(self.refs):
            matched = np.zeros(q.n_windows, dtype=np.int64)
            matched[q.qwins] = sums[:, b]
            out[g.name] = Directed(matched, q.totals)
        return out

    def query_many(self, queries: Sequence[GenomeData]
                   ) -> Dict[str, Dict[str, Directed]]:
        return dict(zip((q.name for q in queries),
                        self.pool.map(self.query, queries)))


class FragmentANI:
    """Memoised directed fragment counts and pair ANI over a job's
    genomes. Representatives stay in `resident` tables while their
    precluster is clustered; any other reference is filled into a
    scratch table, eight at a time."""

    def __init__(self, data: Dict[str, GenomeData], min_af: float,
                 fraglen: int, threads: int, subsample: int = 1) -> None:
        self.data = data
        self.min_af = min_af
        self.fraglen = fraglen
        self.subsample = subsample
        self.counts: Dict[Tuple[str, str], Directed] = {}
        self.pool = ThreadPoolExecutor(max_workers=max(1, threads))
        self.scratch = MembershipTable(self.pool)
        self.resident: List[MembershipTable] = []

    def close(self) -> None:
        self.scratch = None
        self.resident = []
        self.pool.shutdown()

    def _resident_names(self) -> Dict[str, MembershipTable]:
        return {g.name: t for t in self.resident for g in t.refs}

    def keep_resident(self, name: str) -> None:
        if name in self._resident_names():
            return
        if not self.resident or self.resident[-1].full:
            self.resident.append(MembershipTable(self.pool))
        self.resident[-1].add(self.data[name])

    def release_resident(self) -> None:
        for t in self.resident:
            t.clear()
        self.resident = []

    def ensure(self, directed: Sequence[Tuple[str, str]]) -> None:
        """Compute the (query, reference) counts not yet known."""
        todo: Dict[str, set] = {}
        for q, r in directed:
            if (q, r) not in self.counts:
                todo.setdefault(r, set()).add(q)
        if not todo:
            return
        resident = self._resident_names()
        for table in self.resident:
            queries = sorted({q for g in table.refs
                              for q in todo.pop(g.name, ())})
            for q, res in table.query_many(
                    [self.data[q] for q in queries]).items():
                for r, d in res.items():
                    self.counts.setdefault((q, r), d)
        refs = [r for r in _by_component(todo) if r not in resident]
        for c in range(0, len(refs), MembershipTable.BITS):
            chunk = refs[c:c + MembershipTable.BITS]
            for r in chunk:
                self.scratch.add(self.data[r])
            queries = sorted({q for r in chunk for q in todo[r]})
            for q, res in self.scratch.query_many(
                    [self.data[q] for q in queries]).items():
                for r, d in res.items():
                    if q in todo.get(r, ()):
                        self.counts[(q, r)] = d
            self.scratch.clear()

    def directed(self, q: str, r: str, dtype=np.float64):
        return directed_ani(self.counts[(q, r)], self.fraglen, dtype,
                            self.subsample)

    def ani(self, a: str, b: str, dtype=np.float64) -> Optional[float]:
        return combine(self.directed(a, b, dtype),
                       self.directed(b, a, dtype), self.min_af)

    def pairs(self, pairs: Sequence[Tuple[str, str]],
              dtype=np.float64) -> List[Optional[float]]:
        self.ensure([p for a, b in pairs for p in ((a, b), (b, a))])
        return [self.ani(a, b, dtype) for a, b in pairs]


def _by_component(todo: Dict[str, set]) -> List[str]:
    """References ordered so that those sharing queries sit together."""
    parent: Dict[str, str] = {}

    def find(x):
        parent.setdefault(x, x)
        while parent[x] != x:
            x = parent[x]
        return x

    for r, qs in todo.items():
        for q in qs:
            a, b = find(r), find(q)
            if a != b:
                parent[max(a, b)] = min(a, b)
    return sorted(todo, key=lambda r: (find(r), r))


# ---------------------------------------------------------------- quality

def quality_order(records: Sequence[dict], min_completeness: float,
                  max_contamination: float) -> List[str]:
    """Names in quality order. `records` in input order, each with
    name, completeness and contamination (strings, percent),
    num_contigs and num_ambiguous."""
    scored = []
    for r in records:
        comp = float(r["completeness"]) / 100.0
        cont = float(r["contamination"]) / 100.0
        if comp < min_completeness or cont > max_contamination:
            continue
        score = (comp * 100.0 - 5.0 * cont * 100.0
                 - 5.0 * r["num_contigs"] / 100.0
                 - 5.0 * r["num_ambiguous"] / 100000.0)
        scored.append((r["name"], score))
    scored.sort(key=lambda t: -t[1])
    return [name for name, _ in scored]


# ---------------------------------------------------------------- greedy

def components(n: int, pairs) -> List[List[int]]:
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for i, j in pairs:
        a, b = find(i), find(j)
        if a != b:
            parent[max(a, b)] = min(a, b)
    comps: Dict[int, List[int]] = {}
    for g in range(n):
        comps.setdefault(find(g), []).append(g)
    out = sorted(comps.values(), key=lambda c: (-len(c), c[0]))
    return out


class PreclusterANI:
    """The clusterer's ANI where precluster and clusterer are one
    method: the precluster values themselves."""

    def __init__(self, pre: Dict[Tuple[int, int], float]) -> None:
        self.pre = pre

    def begin(self) -> None:
        pass

    def is_rep(self, g: int, cands: Sequence[int], thr: float) -> bool:
        return not any(self.pre[_key(r, g)] >= thr for r in cands)

    def values(self, pairs):
        return [self.pre.get(_key(a, b)) for a, b in pairs]


class ClustererANI:
    """skani as the clusterer, on the fragment ANI of quality-ordered
    names; a failed pair reads 0. A genome that one representative
    already reaches in the genome's own direction (aligned fraction and
    ANI both past their thresholds, so the pair's larger ANI is too) is
    decided without the other direction."""

    def __init__(self, frag: FragmentANI, order: Sequence[str],
                 dtype) -> None:
        self.frag = frag
        self.order = order
        self.dtype = dtype

    def begin(self) -> None:
        self.frag.release_resident()

    def is_rep(self, g: int, cands: Sequence[int], thr: float) -> bool:
        if not cands:
            return True
        f, name = self.frag, self.order[g]
        reps = [self.order[r] for r in cands]
        for r in reps:
            f.keep_resident(r)
        f.ensure([(name, r) for r in reps])
        for r in reps:
            ani, fm, ft = f.directed(name, r, self.dtype)
            if fm > 0 and fm / max(ft, 1) >= f.min_af and ani >= thr:
                return False
        return not any(v >= thr for v in self.values(
            [(r, g) for r in cands]))

    def values(self, pairs):
        vals = self.frag.pairs(
            [(self.order[a], self.order[b]) for a, b in pairs],
            dtype=self.dtype)
        return [0.0 if v is None else v for v in vals]


def _key(a: int, b: int) -> Tuple[int, int]:
    return (a, b) if a < b else (b, a)


def greedy(n: int, pre: Dict[Tuple[int, int], float], thr: float,
           oracle) -> List[List[int]]:
    """Galah's greedy clustering over quality-ordered indices, with the
    clusterer's ANI from `oracle`."""
    clusters: List[List[int]] = []
    for members in components(n, pre.keys()):
        oracle.begin()
        reps: List[int] = []
        for g in members:
            cands = [r for r in reps if _key(r, g) in pre]
            if oracle.is_rep(g, cands, thr):
                reps.append(g)
        rep_set = set(reps)
        others = [g for g in members if g not in rep_set]
        need = [(r, g) for g in others for r in reps if _key(r, g) in pre]
        values = dict(zip(need, oracle.values(need)))
        out = {r: [r] for r in reps}
        for g in others:
            best, best_ani = None, None
            for r in reps:
                v = values.get((r, g))
                if v is not None and (best_ani is None or v > best_ani):
                    best, best_ani = r, v
            if best is None:
                raise RuntimeError(f"genome {g} has no ANI to any "
                                   "representative")
            out[best].append(g)
        clusters += [out[r] for r in reps]
    return clusters


# ---------------------------------------------------------------- a job

class JobResult:
    def __init__(self, order: List[str], pre: Dict[frozenset, float],
                 clusters: List[List[str]],
                 sizes: Dict[str, Tuple[int, int]]) -> None:
        self.order = order          # names in quality order
        self.pre = pre              # precluster pairs -> ANI
        self.clusters = clusters    # names, representative first
        # name -> (query positions, distinct 15-mers), as sampled: the
        # sizes of the fragment-membership test
        self.sizes = sizes
        # the clusterer's ANI of pairs asked for after solving
        self.clusterer: Dict[frozenset, Optional[float]] = {}

    def rows(self) -> List[Tuple[str, str]]:
        return [(c[0], m) for c in self.clusters for m in c]


class Job:
    """One job's catalogue prepared for the reference: quality order,
    per-genome k-mer data, and memoised fragment counts, so that the
    result can be solved more than once (in float64, and in float32 for
    the control) for the price of one membership pass."""

    def __init__(self, config: dict, records: Sequence[dict],
                 contigs: Dict[str, Sequence[np.ndarray]],
                 threads: int) -> None:
        methods = config["methods"]
        self.pre_method = methods["precluster"]
        self.cl_method = methods["cluster"]
        if self.cl_method not in CLUSTER_METHODS:
            raise ValueError(f"no reference for cluster {self.cl_method!r}")
        if self.pre_method not in PRECLUSTER_METHODS:
            raise ValueError(
                f"no reference for precluster {self.pre_method!r}")
        self.ani_thr = methods["ani"] / 100.0
        self.pre_thr = methods["precluster_ani"] / 100.0
        if self.pre_method == "skani" and self.cl_method == "skani":
            self.pre_thr = self.ani_thr
        fraglen = int(methods["fragment_length"])
        subsample = ani_subsample(config)
        quality = config["quality"]
        self.order = quality_order(
            records, quality["min_completeness"] / 100.0,
            quality["max_contamination"] / 100.0)
        self.data = build_genomes(
            [(n, contigs[n]) for n in self.order],
            need_finch=self.pre_method == "finch", need_skani=True,
            fraglen=fraglen, threads=threads, subsample=subsample)
        self.frag = FragmentANI(self.data, methods["min_aligned_fraction"]
                                / 100.0, fraglen, threads, subsample)
        self.screened = (screen_pairs(self.order, self.data)
                         if self.pre_method == "skani" else None)

    def close(self) -> None:
        self.frag.close()

    def clusterer_values(self, pairs: Sequence[Tuple[str, str]],
                         dtype=np.float64) -> List[float]:
        """The clusterer's ANI of these (name, name) pairs: skani's
        fragment ANI, a failed pair reading 0 (as ClustererANI)."""
        return [0.0 if v is None else v
                for v in self.frag.pairs(list(pairs), dtype=dtype)]

    def solve(self, dtype=np.float64, alter_pre=None) -> JobResult:
        """The job's result with ANI arithmetic in `dtype`. `alter_pre`,
        given, rewrites the precluster pairs before clustering (how the
        faults the check must catch are planted)."""
        order = self.order
        if self.pre_method == "finch":
            pre_idx = finch_pairs(order, self.data, self.pre_thr,
                                  dtype=dtype)
        else:
            values = self.frag.pairs(
                [(order[i], order[j]) for i, j in self.screened],
                dtype=dtype)
            pre_idx = {p: v for p, v in zip(self.screened, values)
                       if v is not None and v >= self.pre_thr}
        if alter_pre is not None:
            pre_idx = alter_pre(pre_idx)
        oracle = (PreclusterANI(pre_idx)
                  if self.pre_method == self.cl_method
                  else ClustererANI(self.frag, order, dtype))
        clusters = greedy(len(order), pre_idx, self.ani_thr, oracle)
        self.frag.release_resident()
        return JobResult(
            order,
            {frozenset((order[i], order[j])): v
             for (i, j), v in pre_idx.items()},
            [[order[g] for g in c] for c in clusters],
            {n: (int(g.qids.shape[0]), int(g.distinct.shape[0]))
             for n, g in self.data.items()})


def run_job(config: dict, records: Sequence[dict],
            contigs: Dict[str, Sequence[np.ndarray]], threads: int,
            dtype=np.float64) -> JobResult:
    """The reference result of one job. `records` as for quality_order,
    in input order; `contigs` maps each name to its contigs' codes."""
    job = Job(config, records, contigs, threads)
    try:
        return job.solve(dtype)
    finally:
        job.close()
