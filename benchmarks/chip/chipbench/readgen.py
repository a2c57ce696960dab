"""Reference genome and read generation for the chip benchmark.

Everything here is vectorised numpy, so a cell's whole read pool is made
in set-up and no simulation runs inside the measured window.

* `make_genome`: random sequence with planted repeat families.  Each
  ``family_len`` chunk is, with probability ``repeat_frac``, a copy of
  one of ``n_families`` motifs with ``round(divergence * family_len)``
  random positions mutated (drawn with replacement), else random
  sequence.  Human interspersed repeats are old, diverged families; the
  per-copy divergence is what sets how many seeds stay placeable.
* `simulate_pairs`: F1R2 read pairs with the same error model as the
  program's per-base simulator: at each step an insertion (a random
  base, no reference consumed), a deletion (a reference base skipped),
  a substitution or a match, with probabilities ``ins_rate``,
  ``del_rate``, ``sub_rate`` and the rest, until ``read_len`` bases are
  emitted.  Fragment length ``max(R, int(N(insert_mean, insert_std)))``;
  mate 2 is the reverse complement of the fragment's last ``R`` bases.
* `simulate_long`: substitution-only long reads in reference orientation.
"""
from __future__ import annotations

import numpy as np

#: steps drawn per read beyond its length; deletions emit nothing, so a
#: read needs ``R + n_del`` steps.  A row that runs out raises.
STEP_SLACK = 64


def make_genome(length: int, seed: int, repeat_frac: float,
                n_families: int, family_len: int,
                divergence: float) -> np.ndarray:
    """(length,) uint8 bases (A=0, C=1, G=2, T=3) from ``seed``."""
    rng = np.random.default_rng(seed)
    n_chunks = -(-length // family_len)
    seq = rng.integers(0, 4, size=(n_chunks, family_len), dtype=np.uint8)
    motifs = rng.integers(0, 4, size=(n_families, family_len),
                          dtype=np.uint8)
    rep = np.flatnonzero(rng.random(n_chunks) < repeat_frac)
    fam = rng.integers(0, n_families, size=rep.size)
    k = max(1, int(round(divergence * family_len)))
    copies = motifs[fam]
    cols = rng.integers(0, family_len, size=(rep.size, k))
    bump = rng.integers(1, 4, size=(rep.size, k), dtype=np.uint8)
    rows = np.arange(rep.size)[:, None]
    copies[rows, cols] = (copies[rows, cols] + bump) % 4
    seq[rep] = copies
    return seq.reshape(-1)[:length]


def _emit(genome: np.ndarray, starts: np.ndarray, read_len: int,
          sub_rate: float, ins_rate: float, del_rate: float,
          rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """Sequence ``read_len`` bases from each start; returns (reads, edits)."""
    n = starts.shape[0]
    m = read_len + STEP_SLACK
    u = rng.random((n, m))
    ins = u < ins_rate
    dele = (u >= ins_rate) & (u < ins_rate + del_rate)
    sub = (u >= ins_rate + del_rate) & (u < ins_rate + del_rate + sub_rate)
    emits = ~dele
    emitted = np.cumsum(emits, axis=1)            # bases out after step j
    if (emitted[:, -1] < read_len).any():
        raise RuntimeError("a read ran out of steps; raise STEP_SLACK")
    used = (emitted - emits) < read_len           # step j still needed
    consumes = (~ins).astype(np.int64)
    cursor = np.cumsum(consumes, axis=1) - consumes
    pos = np.minimum(starts[:, None] + cursor, genome.shape[0] - 1)
    base = genome[pos]
    rand_base = rng.integers(0, 4, size=(n, m), dtype=np.uint8)
    rand_bump = rng.integers(1, 4, size=(n, m), dtype=np.uint8)
    val = np.where(ins, rand_base,
                   np.where(sub, (base + rand_bump) % 4, base))
    row, col = np.nonzero(used & emits)
    reads = np.empty((n, read_len), np.uint8)
    reads[row, emitted[row, col] - 1] = val[row, col]
    edits = ((ins | dele | sub) & used).sum(axis=1).astype(np.int32)
    return reads, edits


def simulate_pairs(genome: np.ndarray, n: int, rng: np.random.Generator, *,
                   read_len: int, insert_mean: float, insert_std: float,
                   sub_rate: float, ins_rate: float, del_rate: float,
                   edge_pad: int) -> dict:
    """``n`` F1R2 pairs: reads1 (reference orientation), reads2 (as
    sequenced, reverse strand), their true starts and edit counts."""
    R = read_len
    insert = np.maximum(
        R, rng.normal(insert_mean, insert_std, size=n).astype(np.int64))
    lo, hi = edge_pad, genome.shape[0] - edge_pad
    start1 = rng.integers(lo, hi - insert - R)
    start2 = start1 + insert - R
    reads1, e1 = _emit(genome, start1, R, sub_rate, ins_rate, del_rate, rng)
    fwd2, e2 = _emit(genome, start2, R, sub_rate, ins_rate, del_rate, rng)
    return {"reads1": reads1, "reads2": np.ascontiguousarray((3 - fwd2)[:, ::-1]),
            "true1": start1.astype(np.int32), "true2": start2.astype(np.int32),
            "edits": np.stack([e1, e2], axis=1), "insert": insert}


def simulate_long(genome: np.ndarray, n: int, rng: np.random.Generator, *,
                  read_len: int, sub_rate: float, edge_pad: int) -> dict:
    """``n`` long reads with substitutions at ``sub_rate``."""
    starts = rng.integers(edge_pad, genome.shape[0] - read_len - edge_pad,
                          size=n)
    reads = genome[starts[:, None] + np.arange(read_len)]
    errs = rng.random(reads.shape) < sub_rate
    bump = rng.integers(1, 4, size=int(errs.sum()), dtype=np.uint8)
    reads[errs] = (reads[errs] + bump) % 4
    return {"reads": reads, "true": starts.astype(np.int32),
            "edits": errs.sum(axis=1).astype(np.int32)}


def make_pool(genome: np.ndarray, lane: str, batch: int, traffic: dict,
              seed: int) -> list[dict]:
    """``traffic["pool_batches"]`` whole batches of the cell's traffic."""
    rng = np.random.default_rng(seed)
    params = {k: v for k, v in traffic.items()
              if k not in ("lane", "pool_batches")}
    if traffic["lane"] != lane:
        raise ValueError(f"traffic lane {traffic['lane']!r} does not match "
                         f"the configuration's lane {lane!r}")
    make = simulate_pairs if lane == "pairs" else simulate_long
    return [make(genome, batch, rng, **params)
            for _ in range(traffic["pool_batches"])]
