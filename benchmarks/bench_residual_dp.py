"""Fused banded residual DP vs the staged unbanded fallback (step 5).

The staged baseline is the seed repo's residual stage exactly as
the pipeline wrote it out before the fusion: materialize both mates'
``(cap, R + 2*dp_pad)`` reference windows in HBM and run the unbanded
`gotoh_semiglobal` over every lane of both mates — regardless of which
mate actually failed Light Alignment.  The fused path is one
`residual_pair_dp` call (backend="auto": the Pallas kernel on TPU, the
moving-frame jnp oracle elsewhere): banded DP (O(R*(2*band+1)) per lane
instead of O(R*W)) over only the failed-mate work items.

On CPU the banding alone carries the win (the jnp oracle computes the
same narrow frame); the single-mate item skip and the in-kernel window
DMA are kernel-backend savings that show up on TPU.  Derived columns:
window bytes the staged path materializes, the DP-cell ratio, and the
fused/staged speedup.  The `residual_dp_bitexact` row is CI's hard gate:
interpret-mode kernel == jnp oracle, and ``band >= W`` == the unbanded
`gotoh_semiglobal`, both flavors.

Also writes ``artifacts/bench/BENCH_residual_dp.json`` — the first
point of the perf-trajectory series CI uploads per merge.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_pair, world, write_bench
from repro.core.dp_fallback import gotoh_semiglobal
from repro.core.encoding import pack_2bit
from repro.core.light_align import gather_ref_windows
from repro.core.pipeline import PipelineConfig
from repro.core.seedmap import INVALID_LOC
from repro.kernels.residual_dp import residual_pair_dp

R = 150
SWEEPS = [(256, 16), (1024, 16), (1024, 32)]   # (cap rows, dp_pad)


@functools.partial(jax.jit, static_argnames=("dp_pad",))
def _staged(ref, reads1, reads2, pos1, pos2, dp_pad):
    """Seed-repo math: window gather + full unbanded DP of BOTH mates."""
    def one(reads, pos):
        safe = jnp.where(pos != INVALID_LOC, pos, 0)
        win = gather_ref_windows(ref, safe, R, dp_pad)
        return gotoh_semiglobal(reads, win)

    return one(reads1, pos1), one(reads2, pos2)


def _residuals(ref_len, n, rng):
    pos1 = rng.integers(32, ref_len - R - 32, (n,)).astype(np.int32)
    pos2 = rng.integers(32, ref_len - R - 32, (n,)).astype(np.int32)
    # typical residual mix: mostly one failed mate per row
    need1 = rng.random(n) < 0.55
    need2 = np.where(need1, rng.random(n) < 0.15, True)
    reads1 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    return (jnp.asarray(reads1), jnp.asarray(reads2), jnp.asarray(pos1),
            jnp.asarray(pos2), jnp.asarray(need1), jnp.asarray(need2))


def _verify_bitexact(ref_j, cfg) -> dict:
    """Interpret-mode kernel vs jnp oracle (both flavors, bands across
    the banded/full split), plus the band >= W == gotoh_semiglobal
    anchor."""
    rng = np.random.default_rng(5)
    n, dp_pad = 8, 12
    W = R + 2 * dp_pad
    r1, r2, p1, p2, n1, n2 = _residuals(int(ref_j.shape[0]), n, rng)
    words = jnp.asarray(pack_2bit(ref_j))
    out = {}
    for packed in (False, True):
        ok = True
        for band in (8, cfg.band(), W):
            kw = dict(band=band, scoring=cfg.scoring, packed_ref=packed,
                      block=4)
            got = residual_pair_dp(words if packed else ref_j, r1, r2, p1,
                                   p2, n1, n2, dp_pad,
                                   backend="interpret", **kw)
            want = residual_pair_dp(words if packed else ref_j, r1, r2, p1,
                                    p2, n1, n2, dp_pad, backend="jnp", **kw)
            for f in ("score1", "ref_end1", "score2", "ref_end2"):
                ok &= bool(jnp.array_equal(getattr(got, f),
                                           getattr(want, f)))
        out["packed" if packed else "unpacked"] = ok
    # band >= W recovers the exact unbanded DP on the needed mates
    safe = jnp.where(p1 != INVALID_LOC, p1, 0)
    full = gotoh_semiglobal(r1, gather_ref_windows(ref_j, safe, R, dp_pad))
    anchor = residual_pair_dp(ref_j, r1, r2, p1, p2, n1, n2, dp_pad,
                              band=W, backend="interpret", block=4)
    nd = np.asarray(n1)
    out["band_ge_w_exact"] = bool(
        np.array_equal(np.asarray(anchor.score1)[nd],
                       np.asarray(full.score)[nd]))
    return out


def run() -> list[dict]:
    ref, _, ref_j = world(300_000)
    cfg = PipelineConfig()
    rng = np.random.default_rng(0)
    rows = []
    for cap, dp_pad in SWEEPS:
        W = R + 2 * dp_pad
        band = dp_pad + cfg.max_gap
        r1, r2, p1, p2, n1, n2 = _residuals(len(ref), cap, rng)

        us_staged, us_fused = time_pair(
            lambda: _staged(ref_j, r1, r2, p1, p2, dp_pad),
            lambda: residual_pair_dp(ref_j, r1, r2, p1, p2, n1, n2, dp_pad,
                                     band=band, scoring=cfg.scoring,
                                     backend="auto"))
        shape = f"cap{cap}_R{R}_pad{dp_pad}"
        hbm_mb = 2 * cap * W / 1e6          # uint8 window tensors per call
        cells = round(W / (2 * band + 1), 2)  # full/banded DP-cell ratio
        rows.append(row(f"residual_dp_staged_cap{cap}_pad{dp_pad}",
                        us_staged, shape=shape, backend="jnp",
                        window_mb=round(hbm_mb, 2)))
        rows.append(row(
            f"residual_dp_fused_cap{cap}_pad{dp_pad}", us_fused,
            shape=shape, backend="auto",
            speedup=round(us_staged / max(us_fused, 1e-9), 3),
            dp_cell_ratio=cells))

    t0 = time.perf_counter()
    exact = _verify_bitexact(ref_j, cfg)
    rows.append(row("residual_dp_bitexact",
                    (time.perf_counter() - t0) * 1e6, **{
                        f"bitexact_{k}": v for k, v in exact.items()}))
    # Perf-trajectory point: one JSON per benchmark family, uploaded by
    # CI every merge so the fused-vs-staged ratio is tracked over PRs.
    write_bench("residual_dp", rows)
    # Hard gates, not advisory columns: a kernel/oracle divergence or a
    # fused path slower than the staged baseline on the default shape
    # must fail the benchmark job (run.py exits nonzero on exceptions).
    assert all(exact.values()), exact
    default = next(r for r in rows
                   if r["name"] == "residual_dp_fused_cap1024_pad16")
    assert default["derived"]["speedup"] > 1.0, default
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())
