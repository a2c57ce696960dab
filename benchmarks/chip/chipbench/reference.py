"""Plain reference of what the mapper returns, for the `correct` check.

A straightforward jax.numpy statement of the mapping semantics that the
configuration files state, independent of the program: it imports
nothing from ``repro`` and takes nothing the program made.  Its index is
built here from the genome, for the buckets a batch's seeds hash to: the
genome's every seed position is hashed on the device, the positions that
land in a wanted bucket are kept, and a bucket's locations are its
positions in ascending order, none at all when it holds more than
``max_locations``, and at most ``max_locs_per_seed`` of them per seed.

Pairs (one result row per pair, every `MapResult` field):

1. mate 2 is reverse-complemented into reference orientation;
2. seeds: ``seeds_per_read`` windows of ``seed_len`` bases at offsets
   ``round(i * (R - seed_len) / (S - 1))``, 2-bit packed into four
   little-endian uint32 words, xxHash32, bucket = hash & (T - 1);
3. each location minus its seed's offset is a read start; a read's
   starts are merged ascending (``n_hits`` of them);
4. paired adjacency: occurrence k of a mate-1 start probes the (k+1)-th
   mate-2 start at or above ``start1 - delta``; pairs within ``delta``,
   duplicates dropped, the first ``max_candidates`` in order kept;
5. light alignment of both mates at every candidate: the best of the
   mismatch-only hypothesis and, per gap length 1..E, the one-gap split
   that minimises mismatches; the candidate with the best summed score
   wins (first on ties); a mate passes at ``score >= threshold``;
6. pairs with a candidate whose mates do not both pass go to the
   residual DP buffer, the first ``round(B * residual_capacity_frac)``
   of them in batch order: each failed mate gets a banded semiglobal
   Gotoh score in a window ``dp_pad`` wider on each side, band
   ``dp_pad + max_gap``; the rest overflow.

Long reads (every `LongReadResult` field): segments of ``segment_len``
every ``segment_stride`` bases; consecutive segments are pseudo-pairs
through steps 2-4 with delta widened by the stride; each candidate votes
for the diagonal ``start - i * stride``, binned by ``vote_bin`` (floored);
the most-voted bin wins, the smallest on ties; segment 0 is scored by the
banded Gotoh in a window ``dp_halo`` wider than the segment, centred half
a bin past the voted start, band ``vote_bin // 2 + max_gap``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INVALID = np.int32(2**31 - 1)
NEG = -(1 << 20)
BIG16 = 1 << 14
PRIMES = (2654435761, 2246822519, 3266489917)
#: genome positions hashed per device call
CHUNK = 1 << 24
#: most matching positions one chunk may hold for the wanted buckets
CHUNK_MATCHES = 1 << 22

EDIT_NONE, EDIT_INS, EDIT_DEL = 0, 1, 2
CIG_M, CIG_I, CIG_D = 0, 1, 2


# ------------------------------------------------------------ hashing --
def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def xxhash32(words, seed: int):
    """xxHash32 of a 16-byte message given as (..., 4) uint32 words."""
    p1, p2, p3 = (jnp.uint32(p) for p in PRIMES)
    s = jnp.uint32(seed)

    def rnd(acc, lane):
        return _rotl(acc + lane * p2, 13) * p1

    v = (rnd(s + p1 + p2, words[..., 0]), rnd(s + p2, words[..., 1]),
         rnd(s, words[..., 2]), rnd(s - p1, words[..., 3]))
    acc = _rotl(v[0], 1) + _rotl(v[1], 7) + _rotl(v[2], 12) + _rotl(v[3], 18)
    acc = acc + jnp.uint32(16)
    acc = (acc ^ (acc >> jnp.uint32(15))) * p2
    acc = (acc ^ (acc >> jnp.uint32(13))) * p3
    return acc ^ (acc >> jnp.uint32(16))


def seed_offsets(read_len: int, seed_len: int, n_seeds: int) -> np.ndarray:
    if n_seeds == 1:
        return np.zeros(1, np.int32)
    span = read_len - seed_len
    return np.round(np.arange(n_seeds) * span / (n_seeds - 1)).astype(np.int32)


def _pack(seeds):
    """(..., seed_len <= 64) bases -> (..., 4) uint32, 16 bases a word."""
    n = seeds.shape[-1]
    pad = 64 - n
    s = jnp.concatenate(
        [seeds.astype(jnp.uint32),
         jnp.zeros(seeds.shape[:-1] + (pad,), jnp.uint32)], -1)
    s = s.reshape(seeds.shape[:-1] + (4, 16))
    return (s << (2 * jnp.arange(16, dtype=jnp.uint32))).sum(
        -1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("sm",))
def read_buckets(reads, sm: tuple):
    """(N, R) reads -> (N, S) bucket ids.  ``sm`` = (seed_len, n_seeds,
    hash_seed, table_bits)."""
    seed_len, n_seeds, hash_seed, table_bits = sm
    offs = seed_offsets(reads.shape[-1], seed_len, n_seeds)
    seeds = reads[:, offs[:, None] + np.arange(seed_len)]
    h = xxhash32(_pack(seeds), hash_seed)
    return (h & jnp.uint32((1 << table_bits) - 1)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sm", "chunk", "cap"))
def _chunk_matches(genome, start, n_pos, wanted, sm: tuple, chunk: int,
                   cap: int):
    """Seed positions in [start, start + chunk) below ``n_pos`` whose
    bucket is wanted: (positions, buckets, count), ascending, -1 padded.
    ``genome`` carries 64 bases of padding past its last chunk."""
    seed_len, _, hash_seed, table_bits = sm
    seg = jax.lax.dynamic_slice(genome, (start,), (chunk + 64,))
    seg = seg.astype(jnp.uint32)
    words = []
    for j in range(4):
        w = jnp.zeros((chunk,), jnp.uint32)
        for i in range(16):
            if 16 * j + i < seed_len:
                w = w | (seg[16 * j + i:16 * j + i + chunk]
                         << jnp.uint32(2 * i))
        words.append(w)
    h = xxhash32(jnp.stack(words, -1), hash_seed)
    bucket = (h & jnp.uint32((1 << table_bits) - 1)).astype(jnp.int32)
    pos = start + jnp.arange(chunk, dtype=jnp.int32)
    hit = wanted[bucket] & (pos < n_pos)
    (idx,) = jnp.nonzero(hit, size=cap, fill_value=0)
    count = hit.sum()
    keep = jnp.arange(cap) < count
    return (jnp.where(keep, pos[idx], -1), jnp.where(keep, bucket[idx], -1),
            count)


class SubIndex:
    """The seed index restricted to a set of buckets."""

    def __init__(self, genome_dev, buckets: np.ndarray, index: dict):
        self.cfg = index
        sm = _sm_key(index)
        T = 1 << index["table_bits"]
        wanted = np.zeros(T, bool)
        wanted[np.unique(buckets)] = True
        wanted = jnp.asarray(wanted)
        L = genome_dev.shape[0]
        chunk = min(CHUNK, 1 << (L - 1).bit_length())
        cap = min(CHUNK_MATCHES, chunk)
        n_chunks = -(-L // chunk)
        padded = jnp.concatenate(
            [genome_dev, jnp.zeros(n_chunks * chunk + 64 - L, jnp.uint8)])
        n_pos = jnp.int32(L - index["seed_len"] + 1)
        pos, bkt = [], []
        for c in range(n_chunks):
            p, b, n = _chunk_matches(padded, jnp.int32(c * chunk), n_pos,
                                     wanted, sm, chunk, cap)
            n = int(n)
            if n > cap:
                raise RuntimeError(f"{n} seed positions in one chunk match "
                                   "the wanted buckets; raise CHUNK_MATCHES")
            pos.append(np.asarray(p[:n]))
            bkt.append(np.asarray(b[:n]))
        pos = np.concatenate(pos)
        bkt = np.concatenate(bkt)
        order = np.argsort(bkt, kind="stable")     # positions stay ascending
        self.sorted_bucket = bkt[order]
        self.sorted_pos = pos[order]

    def locations(self, buckets: np.ndarray) -> np.ndarray:
        """(...,) bucket ids -> (..., K) locations, INVALID padded."""
        K = self.cfg["max_locs_per_seed"]
        lo = np.searchsorted(self.sorted_bucket, buckets, side="left")
        hi = np.searchsorted(self.sorted_bucket, buckets, side="right")
        count = hi - lo
        count = np.where(count > self.cfg["max_locations"], 0,
                         np.minimum(count, K))
        k = np.arange(K)
        idx = np.minimum(lo[..., None] + k, max(len(self.sorted_pos) - 1, 0))
        locs = self.sorted_pos[idx] if len(self.sorted_pos) else \
            np.zeros(idx.shape, np.int32)
        return np.where(k < count[..., None], locs, INVALID).astype(np.int32)


def _sm_key(index: dict) -> tuple:
    return (index["seed_len"], index["seeds_per_read"], index["hash_seed"],
            index["table_bits"])


# ------------------------------------------------------ front end ------
def merge_starts(locs, offs):
    """(N, S, K) locations -> ((N, S*K) ascending starts, (N,) count)."""
    valid = locs != INVALID
    starts = jnp.where(valid, locs - jnp.asarray(offs)[None, :, None],
                       INVALID)
    flat = jnp.sort(starts.reshape(starts.shape[0], -1), axis=-1)
    return flat, valid.reshape(valid.shape[0], -1).sum(-1).astype(jnp.int32)


def _row_filter(s1, s2, delta, cap):
    M = s1.shape[0]
    lo = jnp.searchsorted(s2, s1 - delta, side="left")
    occ = jnp.arange(M, dtype=lo.dtype) - jnp.searchsorted(s1, s1,
                                                           side="left")
    p2 = s2[jnp.clip(lo + occ, 0, M - 1)]
    within = (p2 != INVALID) & (jnp.abs(p2 - s1) <= delta) & (s1 != INVALID)
    first = jnp.concatenate(
        [jnp.array([True]), (s1[1:] != s1[:-1]) | (p2[1:] != p2[:-1])])
    keep = within & first
    take = jnp.argsort(~keep, stable=True)[:cap]
    ok = keep[take]
    pos1 = jnp.where(ok, s1[take], INVALID)
    pos2 = jnp.where(ok, p2[take], INVALID)
    if cap > M:
        pad = jnp.full((cap - M,), INVALID, jnp.int32)
        pos1, pos2 = jnp.concatenate([pos1, pad]), jnp.concatenate([pos2, pad])
    return pos1, pos2, jnp.minimum(keep.sum(), cap).astype(jnp.int32)


def adjacency(s1, s2, delta: int, cap: int):
    return jax.vmap(_row_filter, in_axes=(0, 0, None, None))(
        s1, s2, jnp.int32(delta), cap)


# ------------------------------------------------ light alignment ------
def windows(genome, starts, read_len: int, lead: int):
    idx = starts[..., None] + jnp.arange(-lead, read_len + lead,
                                         dtype=jnp.int32)
    return genome[jnp.clip(idx, 0, genome.shape[0] - 1)]


def light_align(read, win, E: int, sc: dict, threshold: int):
    """(N, R) reads against (N, R + 2E) windows -> per-read best of the
    one-gap hypotheses: (score, ok, edit_type, edit_len, edit_pos)."""
    R = read.shape[-1]
    m2 = sc["match"] + sc["mismatch"]
    masks = jnp.stack([win[:, s:s + R] for s in range(2 * E + 1)], 1) \
        != read[:, None, :]
    cum = jnp.concatenate(
        [jnp.zeros(masks.shape[:-1] + (1,), jnp.int16),
         jnp.cumsum(masks.astype(jnp.int16), -1)], -1)
    cum0 = cum[:, E, :]
    p = jnp.arange(R + 1, dtype=jnp.int32)
    mm = cum[:, E, R].astype(jnp.int32)
    hyps = [(sc["match"] * R - m2 * mm, EDIT_NONE, jnp.zeros_like(mm),
             jnp.zeros_like(mm))]

    def best_split(cand, interior, score_of, kind, k):
        cand = jnp.where(interior[None, :], cand, BIG16)
        at = jnp.argmin(cand, -1).astype(jnp.int32)
        n = jnp.take_along_axis(cand, at[:, None], -1)[:, 0].astype(jnp.int32)
        gap = sc["gap_open"] + sc["gap_extend"] * k
        s = jnp.where(n >= BIG16, -(1 << 20), score_of - m2 * n - gap)
        hyps.append((s, kind, jnp.full_like(n, k), at))

    for k in range(1, E + 1):
        cd = cum[:, E + k, :]
        best_split(cum0 + (cd[:, R:R + 1] - cd), (p >= 1) & (p <= R - 1),
                   sc["match"] * R, EDIT_DEL, k)
        ci = cum[:, E - k, :]
        shifted = jnp.concatenate(
            [ci[:, k:], jnp.zeros((ci.shape[0], k), jnp.int16)], -1)
        best_split(cum0 + (ci[:, R:R + 1] - shifted),
                   (p >= 1) & (p <= R - k - 1), sc["match"] * (R - k),
                   EDIT_INS, k)
    scores = jnp.stack([h[0] for h in hyps], -1)
    best = jnp.argmax(scores, -1)

    def pick(i):
        col = jnp.stack([jnp.broadcast_to(jnp.asarray(h[i], jnp.int32),
                                          best.shape) for h in hyps], -1)
        return jnp.take_along_axis(col, best[:, None], -1)[:, 0]

    score = pick(0)
    return score, score >= threshold, pick(1), pick(2), pick(3)


def cigars(etype, elen, epos, R: int):
    none, ins = etype == EDIT_NONE, etype == EDIT_INS
    len0 = jnp.where(none, R, epos)
    op1 = jnp.where(ins, CIG_I, CIG_D)
    len1 = jnp.where(none, 0, elen)
    len2 = jnp.where(none, 0, jnp.where(ins, R - epos - elen, R - epos))
    m = jnp.full_like(etype, CIG_M)
    return jnp.stack([jnp.stack([m, len0], -1), jnp.stack([op1, len1], -1),
                      jnp.stack([m, len2], -1)], 1).astype(jnp.int32)


# ------------------------------------------------------------- DP -----
def gotoh_banded(read, win, band: int, sc: dict):
    """Semiglobal Gotoh (read global, window ends free) over the cells
    within ``band`` of the diagonal through column (W - R) // 2; returns
    the best last-row score."""
    B, R = read.shape
    W = win.shape[-1]
    if band >= W:
        raise ValueError("the reference states a band narrower than the window")
    c = (W - R) // 2
    K = 2 * band + 1
    match, mis = sc["match"], sc["mismatch"]
    open_, ext = sc["gap_open"], sc["gap_extend"]
    k_idx = jnp.arange(K, dtype=jnp.int32)
    neg_col = jnp.full((B, 1), NEG, jnp.int32)
    pad = jnp.full((B, band + 1), -1, jnp.int32)
    win_pad = jnp.concatenate([pad, win.astype(jnp.int32), pad], 1)
    j0 = c - band + k_idx
    h0 = jnp.broadcast_to(jnp.where((j0 >= 0) & (j0 <= W), 0, NEG)[None],
                          (B, K)).astype(jnp.int32)

    def row(carry, x):
        h_prev, e_prev = carry
        col, i = x
        jcol = (i + 1 + c - band) + k_idx
        valid = ((jcol >= 0) & (jcol <= W))[None, :]
        h_up = jnp.concatenate([h_prev[:, 1:], neg_col], -1)
        e_up = jnp.concatenate([e_prev[:, 1:], neg_col], -1)
        e = jnp.maximum(h_up - open_ - ext, e_up - ext)
        wrow = jax.lax.dynamic_slice_in_dim(win_pad, i + c + 1, K, axis=1)
        h = jnp.maximum(h_prev + jnp.where(col[:, None] == wrow, match, -mis),
                        e)
        h = jnp.where(jcol[None, :] == 0, -(open_ + ext * (i + 1)), h)
        h = jnp.where(valid, h, NEG)
        run = jax.lax.cummax(h + ext * k_idx[None, :], axis=1)
        f = jnp.concatenate([neg_col, run[:, :-1]], -1) - open_ \
            - ext * k_idx[None, :]
        h = jnp.where(valid, jnp.maximum(h, f), NEG)
        return (h, e), None

    (h_last, _), _ = jax.lax.scan(
        row, (h0, jnp.full((B, K), NEG, jnp.int32)),
        (read.T.astype(jnp.int32), jnp.arange(R, dtype=jnp.int32)))
    return jnp.max(h_last, -1)


# ----------------------------------------------------------- lanes ----
@functools.partial(jax.jit, static_argnames=("cfg",))
def _pairs(genome, reads1, reads2_fwd, locs1, locs2, cfg: tuple):
    p = dict(cfg)
    sc = dict(p["scoring"])
    B, R = reads1.shape
    E, C = p["max_gap"], p["max_candidates"]
    offs = seed_offsets(R, p["seed_len"], p["seeds_per_read"])
    s1, n1 = merge_starts(locs1, offs)
    s2, n2 = merge_starts(locs2, offs)
    had_hits = (n1 > 0) & (n2 > 0)
    cpos1, cpos2, ncand = adjacency(s1, s2, p["delta"], C)
    passed = ncand > 0
    threshold = p["accept_threshold"]

    def align(reads, cpos):
        valid = cpos != INVALID
        win = windows(genome, jnp.where(valid, cpos, 0), R, E)
        out = light_align(
            jnp.broadcast_to(reads[:, None], (B, C, R)).reshape(B * C, R),
            win.reshape(B * C, -1), E, sc, threshold)
        score = jnp.where(valid.reshape(-1), out[0], -(1 << 20))
        return [o.reshape(B, C) for o in (score,) + out[1:]]

    a1, a2 = align(reads1, cpos1), align(reads2_fwd, cpos2)
    best = jnp.argmax(a1[0] + a2[0], -1)[:, None]

    def take(x):
        return jnp.take_along_axis(x, best, 1)[:, 0]

    bpos1, bpos2 = take(cpos1), take(cpos2)
    sc1, sc2 = take(a1[0]), take(a2[0])
    ok1 = take(a1[1]) & (bpos1 != INVALID)
    ok2 = take(a2[1]) & (bpos2 != INVALID)
    cig1 = cigars(take(a1[2]), take(a1[3]), take(a1[4]), R)
    cig2 = cigars(take(a2[2]), take(a2[3]), take(a2[4]), R)
    light_ok = passed & ok1 & ok2

    needs_dp = passed & ~light_ok
    cap = max(1, int(round(B * p["residual_capacity_frac"])))
    rank = jnp.cumsum(needs_dp.astype(jnp.int32)) - 1
    dp_done = needs_dp & (rank < cap)
    need1, need2 = dp_done & ~ok1, dp_done & ~ok2
    pad, band = p["dp_pad"], p["dp_band"]

    def dp(reads, bpos):
        win = windows(genome, jnp.where(bpos != INVALID, bpos, 0), R, pad)
        return gotoh_banded(reads, win, band, sc)

    d1 = jnp.where(need1, dp(reads1, bpos1), sc1)
    d2 = jnp.where(need2, dp(reads2_fwd, bpos2), sc2)
    dp_overflow = needs_dp & ~dp_done
    method = jnp.where(passed, 0, 3)
    method = jnp.where(light_ok, 1, method)
    method = jnp.where(dp_done, 2, method)
    method = jnp.where(dp_overflow, 4, method)
    mapped = light_ok | dp_done
    return {
        "pos1": jnp.where(mapped, bpos1, INVALID),
        "pos2": jnp.where(mapped, bpos2, INVALID),
        "score1": jnp.where(light_ok, sc1, jnp.where(dp_done, d1, NEG)),
        "score2": jnp.where(light_ok, sc2, jnp.where(dp_done, d2, NEG)),
        "method": method.astype(jnp.int32),
        "cigar1": cig1, "cigar2": cig2,
        "had_hits": had_hits, "passed_adjacency": passed,
        "light_ok": light_ok, "dp_mate1": need1, "dp_mate2": need2,
        "n_valid": jnp.ones((B,), bool),
    }


@functools.partial(jax.jit, static_argnames=("cfg",))
def _long(genome, reads, locs, cfg: tuple):
    p = dict(cfg)
    sc = dict(p["scoring"])
    seg_len, stride = p["segment_len"], p["segment_stride"]
    B = reads.shape[0]
    S = locs.shape[0] // B
    C = p["max_candidates"]
    segs = segment_views(reads, seg_len, stride)
    starts, _ = merge_starts(locs, seed_offsets(seg_len, p["seed_len"],
                                                p["seeds_per_read"]))
    starts = starts.reshape(B, S, -1)
    cpos1, _, ncand = adjacency(
        starts[:, :-1].reshape(B * (S - 1), -1),
        starts[:, 1:].reshape(B * (S - 1), -1), stride + p["delta"], C)
    off = jnp.arange(S - 1, dtype=jnp.int32) * stride
    c = cpos1.reshape(B, S - 1, C)
    diag = jnp.where(c != INVALID, c - off[None, :, None], INVALID)
    diag = diag.reshape(B, -1)
    vote_bin = p["vote_bin"]
    vb = jnp.where(diag != INVALID, jnp.floor_divide(diag, vote_bin), INVALID)
    same = (vb[:, :, None] == vb[:, None, :]) & (vb != INVALID)[:, None, :]
    count = jnp.where(vb != INVALID, same.sum(-1), 0).astype(jnp.int32)
    votes = count.max(-1)
    top = (count == votes[:, None]) & (vb != INVALID)
    win_bin = jnp.where(votes > 0, jnp.where(top, vb, INVALID).min(-1), 0)
    mapped = votes > 0
    position = win_bin * vote_bin
    halo = p["dp_halo"]
    width = seg_len + 2 * halo
    centre = jnp.clip(jnp.where(mapped, position + vote_bin // 2, 0),
                      halo - width, genome.shape[0] - 1 + halo)
    win = windows(genome, centre, seg_len, halo)
    score = gotoh_banded(segs[:, 0], win, vote_bin // 2 + p["max_gap"], sc)
    return {
        "position": jnp.where(mapped, position, INVALID).astype(jnp.int32),
        "votes": votes,
        "score": jnp.where(mapped, score, NEG),
        "mapped": mapped,
        "n_candidates": ncand.reshape(B, S - 1).sum(-1).astype(jnp.int32),
        "n_valid": jnp.ones((B,), bool),
    }


def segment_views(reads, seg_len: int, stride: int):
    n_seg = (reads.shape[-1] - seg_len) // stride + 1
    idx = np.arange(n_seg)[:, None] * stride + np.arange(seg_len)[None, :]
    return reads[:, idx]


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, _frozen(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def map_batches(genome_dev, batches: list, params: dict, lane: str) -> list:
    """Reference results (host arrays) for batches of the cell's pool.

    ``params`` holds the configuration's stated numbers: ``index``
    (seed_len, seeds_per_read, hash_seed, table_bits, max_locations,
    max_locs_per_seed), ``pipeline`` and ``long_read`` (`cell.Cell`).
    One genome scan serves every batch.
    """
    sm = params["index"]
    key = _sm_key(sm)
    if lane == "pairs":
        reads = [(jnp.asarray(b["reads1"]),
                  (3 - jnp.asarray(b["reads2"]))[:, ::-1]) for b in batches]
        buckets = [(np.asarray(read_buckets(r1, key)),
                    np.asarray(read_buckets(r2, key))) for r1, r2 in reads]
    else:
        p = params["long_read"]
        reads = [jnp.asarray(b["reads"]) for b in batches]
        buckets = [np.asarray(read_buckets(
            segment_views(r, p["segment_len"], p["segment_stride"])
            .reshape(-1, p["segment_len"]), key)) for r in reads]
    index = SubIndex(genome_dev, np.concatenate(
        [np.ravel(b) for b in jax.tree.leaves(buckets)]), sm)
    out = []
    for r, b in zip(reads, buckets):
        if lane == "pairs":
            res = _pairs(genome_dev, r[0], r[1],
                         jnp.asarray(index.locations(b[0])),
                         jnp.asarray(index.locations(b[1])),
                         _frozen(params["pipeline"]))
        else:
            res = _long(genome_dev, r, jnp.asarray(index.locations(b)),
                        _frozen(params["long_read"]))
        out.append({k: np.asarray(v) for k, v in res.items()})
    return out
