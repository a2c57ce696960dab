"""Per-(backend, kernel family, shape bucket) autotuner + tune cache.

Every fused kernel family hand-picks its launch geometry
(``DEFAULT_BLOCK`` / ``LAUNCH_ROWS``) and the pipeline hand-picks the
semantic perf knobs (``prescreen_top``, ``dp_band``, the ``packed_ref``
tri-state).  The candidate_align bench already shows the cost of getting
these wrong: at C=8 without prescreen the fused op *loses* to the staged
jnp oracle — the configuration sensitivity the GenPairX co-design sweeps
(filter threshold vs. DP load) and GateSeeder's per-platform tuning warn
about.  This module closes the loop:

  * `tune_session` micro-benchmarks each family over a small knob grid —
    **always including the staged-jnp oracle as a candidate**, so a
    fused config that loses to staged can never win — and persists the
    winners to a JSON cache under ``artifacts/tune/``.
  * `Mapper.build` / `from_index` consult the cache exactly once, at
    session build, next to the existing backend/`packed_ref` resolution
    (`engine/config.py`); nothing on the per-batch path re-reads it.

Cache resolution order (per knob): **explicit config > tune cache >
hand-picked defaults** — a knob the caller set on `PipelineConfig` /
`ExecutionConfig` is never overridden by a cached winner.

Cache file format (version 1)::

    {"version": 1,
     "entries": {
       "<backend>/<family>/<bucket>": {
         "params": {"block": 16, "prescreen_top": 4, ...},
         "us": 812.4, "staged_us": 1203.0,
         "meta": {"batch": 1024, "platform": "cpu", ...}}}}

Keys lead with the *resolved session backend* of the family (the tuner
and the consumer must agree on it); ``params["backend"]`` — present when
the staged oracle or another backend won outright — is applied only when
the caller left the family backend on ``"auto"``.  The cache location is
``artifacts/tune/tune_cache.json``, overridable via the
``REPRO_TUNE_CACHE`` env var (the same env-driven-config idiom as
``REPRO_BACKEND``).

Retuning for a new backend/platform is one command::

    PYTHONPATH=src python -m repro.tune --batch 1024

TPU bring-up is precisely this retune: same sweeps, pallas candidates.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.long_read import LongReadConfig
from repro.core.pipeline import PipelineConfig
from repro.kernels.backend import resolve_backend

CACHE_VERSION = 1
ENV_CACHE = "REPRO_TUNE_CACHE"
DEFAULT_CACHE = os.path.join("artifacts", "tune", "tune_cache.json")

#: The tuned kernel families, in pipeline order.
FAMILIES = ("pair_frontend", "candidate_align", "residual_dp",
            "location_vote")

#: Launch-block grids per family (the hand-picked default is always a
#: candidate; see each family's kernel.py DEFAULT_BLOCK).  The front
#: end puts a read on each lane, so its blocks are multiples of 128.
BLOCK_GRID = {
    "pair_frontend": (128, 256),
    "candidate_align": (8, 16, 32),
    "residual_dp": (16, 32, 64),
    "location_vote": (32, 64, 128),
}


# --------------------------------------------------------------- cache --
def cache_path(path: str | os.PathLike | None = None) -> str:
    """Resolve the cache file path: explicit arg > REPRO_TUNE_CACHE > default."""
    if path:
        return os.fspath(path)
    return os.environ.get(ENV_CACHE) or DEFAULT_CACHE


def load_cache(path: str | os.PathLike | None = None) -> dict:
    """Load the tune-cache entries dict; corrupt/stale files degrade to
    the hand-picked defaults (empty dict) with a warning, never an error."""
    p = cache_path(path)
    if not os.path.exists(p):
        return {}
    try:
        with open(p) as f:
            data = json.load(f)
        if (not isinstance(data, dict)
                or data.get("version") != CACHE_VERSION
                or not isinstance(data.get("entries"), dict)):
            raise ValueError(
                f"expected {{'version': {CACHE_VERSION}, 'entries': ...}}")
        return data["entries"]
    except Exception as e:  # noqa: BLE001 — any corrupt cache degrades
        warnings.warn(
            f"ignoring unreadable tune cache {p!r} ({e!r}); "
            "falling back to hand-picked kernel defaults", stacklevel=2)
        return {}


def save_cache(entries: dict, path: str | os.PathLike | None = None) -> str:
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    with open(p, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
    return p


def session_cache(tune: bool | str | None) -> dict:
    """Resolve `ExecutionConfig.tune` to cache entries, once per build.

    ``False`` — never tune.  A string — that cache file.  ``True`` — the
    default location (env override honored).  ``None`` (the default) —
    opt-in via env only: consult the cache iff ``REPRO_TUNE_CACHE`` is
    set, so sessions stay bit-stable unless the user asks for tuning.
    """
    if tune is False or tune is None and not os.environ.get(ENV_CACHE):
        return {}
    return load_cache(None if tune is True or tune is None else tune)


# ------------------------------------------------------ buckets/lookup --
def _bucket_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


def pipeline_buckets(cfg: PipelineConfig, batch: int,
                     lr_cfg: LongReadConfig | None = None) -> dict:
    """family -> shape-bucket string for a session's pipeline geometry.

    The batch dimension is bucketed to the next power of two (the tuner
    and the consumer rarely agree on the exact stream batch); the static
    shape knobs (seeds, caps, read length, pads) are exact.
    """
    b = _bucket_pow2(batch)
    out = {
        "pair_frontend": (f"B{b}_S{cfg.seeds_per_read}"
                          f"_K{cfg.max_locs_per_seed}"
                          f"_C{cfg.max_candidates}_R{cfg.read_len}"),
        "candidate_align": (f"B{b}_C{cfg.max_candidates}"
                            f"_R{cfg.read_len}_E{cfg.max_gap}"),
        "residual_dp": (f"B{_bucket_pow2(max(1, cfg.residual_cap(batch)))}"
                        f"_R{cfg.read_len}_pad{cfg.dp_pad}"),
    }
    if lr_cfg is not None:
        out["location_vote"] = f"B{b}_bin{lr_cfg.vote_bin}"
    return out


def entry_key(backend: str, family: str, bucket: str) -> str:
    return f"{backend}/{family}/{bucket}"


def _split_bucket(bucket: str) -> tuple[int, str]:
    head, _, rest = bucket.partition("_")
    return int(head[1:]), rest


def lookup(entries: dict, backend: str, family: str, bucket: str):
    """Exact-key lookup with a nearest-batch fallback.

    Falls back to the entry whose batch bucket is (log-scale) closest
    among same-backend/family/static-shape entries — a cache tuned at
    B=1024 still serves a B=512 session rather than silently detuning.
    """
    hit = entries.get(entry_key(backend, family, bucket))
    if hit is not None:
        return hit
    try:
        want_b, suffix = _split_bucket(bucket)
    except ValueError:
        return None
    best = None
    for k, v in entries.items():
        parts = k.split("/", 2)
        if len(parts) != 3 or parts[0] != backend or parts[1] != family:
            continue
        try:
            got_b, got_suffix = _split_bucket(parts[2])
        except ValueError:
            continue
        if got_suffix != suffix:
            continue
        d = abs(np.log2(max(got_b, 1)) - np.log2(max(want_b, 1)))
        if best is None or d < best[0]:
            best = (d, v)
    return best[1] if best else None


# ------------------------------------------------- config application --
def _family_backends(pipe_cfg: PipelineConfig, exec_backend: str | None):
    """The would-be resolved backend per family (the cache key prefix)."""
    return {
        "pair_frontend": resolve_backend(
            exec_backend or pipe_cfg.frontend_backend,
            family="pair_frontend"),
        "candidate_align": resolve_backend(
            exec_backend or pipe_cfg.light_backend,
            family="candidate_align"),
        "residual_dp": resolve_backend(
            exec_backend or pipe_cfg.residual_backend,
            family="residual_dp"),
    }


def apply_tuned_pipeline(pipe_cfg: PipelineConfig, entries: dict,
                         batch: int, exec_backend: str | None = None,
                         exec_packed: bool | None = None
                         ) -> PipelineConfig:
    """Fill *unset* `PipelineConfig` perf knobs from the tune cache.

    Resolution order per knob: explicit config > tune cache > defaults.
    A knob already set (non-None block, explicit ``prescreen_top`` /
    ``dp_band`` / ``packed_ref``, a non-"auto" family backend or a
    session-wide ``ExecutionConfig.backend``) is left alone; everything
    else takes the cached winner when one exists for the session's
    resolved backend and shape bucket.
    """
    if not entries:
        return pipe_cfg
    backends = _family_backends(pipe_cfg, exec_backend)
    buckets = pipeline_buckets(pipe_cfg, batch)
    upd: dict = {}

    def _backend_from(params, family, cfg_backend, field):
        # A cached backend winner (e.g. staged-jnp beating the fused op)
        # applies only when the caller didn't force one anywhere.
        if (params.get("backend") and exec_backend is None
                and cfg_backend == "auto"):
            upd[field] = params["backend"]

    e = lookup(entries, backends["pair_frontend"], "pair_frontend",
               buckets["pair_frontend"])
    if e:
        p = e.get("params", {})
        if pipe_cfg.frontend_block is None and p.get("block"):
            upd["frontend_block"] = int(p["block"])
        _backend_from(p, "pair_frontend", pipe_cfg.frontend_backend,
                      "frontend_backend")

    e = lookup(entries, backends["candidate_align"], "candidate_align",
               buckets["candidate_align"])
    if e:
        p = e.get("params", {})
        if pipe_cfg.light_block is None and p.get("block"):
            upd["light_block"] = int(p["block"])
        if pipe_cfg.prescreen_top is None and "prescreen_top" in p:
            upd["prescreen_top"] = int(p["prescreen_top"])
        if (pipe_cfg.packed_ref is None and exec_packed is None
                and "packed_ref" in p):
            upd["packed_ref"] = bool(p["packed_ref"])
        _backend_from(p, "candidate_align", pipe_cfg.light_backend,
                      "light_backend")

    e = lookup(entries, backends["residual_dp"], "residual_dp",
               buckets["residual_dp"])
    if e:
        p = e.get("params", {})
        if pipe_cfg.residual_block is None and p.get("block"):
            upd["residual_block"] = int(p["block"])
        if pipe_cfg.dp_band is None and p.get("dp_band") is not None:
            upd["dp_band"] = int(p["dp_band"])
        _backend_from(p, "residual_dp", pipe_cfg.residual_backend,
                      "residual_backend")

    return dataclasses.replace(pipe_cfg, **upd) if upd else pipe_cfg


def apply_tuned_long_read(lr_cfg: LongReadConfig, entries: dict,
                          batch: int, exec_backend: str | None = None
                          ) -> LongReadConfig:
    """The lane analogue of `apply_tuned_pipeline` (location_vote knobs;
    the lane's ``pipe`` is tuned by the caller through the pipeline path)."""
    if not entries:
        return lr_cfg
    backend = resolve_backend(exec_backend or lr_cfg.vote_backend,
                              family="location_vote")
    bucket = pipeline_buckets(lr_cfg.pipe, batch, lr_cfg)["location_vote"]
    e = lookup(entries, backend, "location_vote", bucket)
    if not e:
        return lr_cfg
    p = e.get("params", {})
    upd: dict = {}
    if lr_cfg.vote_block is None and p.get("block"):
        upd["vote_block"] = int(p["block"])
    if (p.get("backend") and exec_backend is None
            and lr_cfg.vote_backend == "auto"):
        upd["vote_backend"] = p["backend"]
    return dataclasses.replace(lr_cfg, **upd) if upd else lr_cfg


# -------------------------------------------------------------- tuner --
def _time_candidates(cands: list[tuple[str, dict, object]],
                     reps: int = 3) -> dict:
    """Counterbalanced timing: warm every candidate (compile), then time
    them round-robin so drift hits all candidates alike.  Returns
    label -> median us.

    Off the chip a candidate that fails to run is dropped with a warning.
    On a TPU a failing kernel candidate raises: dropping it would let the
    staged jnp oracle "win" the tune and the cached backend winner would
    silently keep every later session off the kernels.
    """
    live = []
    for label, params, fn in cands:
        try:
            jax.block_until_ready(fn())
            live.append((label, params, fn, []))
        except Exception as e:  # noqa: BLE001 — a bad config is a skip
            if (jax.default_backend() == "tpu"
                    and params.get("backend") != "jnp"):
                raise RuntimeError(
                    f"tune candidate {label!r} failed on the TPU; a kernel "
                    "the chip refuses is a bug, not a slower config") from e
            warnings.warn(f"tune candidate {label!r} failed: {e!r}",
                          stacklevel=2)
    for _ in range(reps):
        for _, _, fn, ts in live:
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
    return {label: (params, float(np.median(ts) * 1e6))
            for label, params, _, ts in live}


def _winner(timed: dict, staged_label: str) -> tuple[dict, float, float]:
    """(winning params, winner us, staged us).  The staged oracle is a
    real candidate, so a fused config slower than staged cannot win."""
    label = min(timed, key=lambda k: timed[k][1])
    staged_us = timed.get(staged_label, (None, float("nan")))[1]
    params, us = timed[label]
    return dict(params), us, staged_us


def tune_session(ref, sm, pipe_cfg: PipelineConfig | None = None,
                 exec_cfg=None, *, batch: int = 1024,
                 lr_cfg: LongReadConfig | None = None,
                 families=FAMILIES, reps: int = 3, seed: int = 0,
                 path: str | os.PathLike | None = None,
                 save: bool = True) -> dict:
    """Micro-benchmark each family's knob grid and persist the winners.

    ``ref`` is the (L,) uint8 reference, ``sm`` the CSR `SeedMap` (or a
    `PaddedSeedMap`).  The workload is synthetic reads simulated from
    ``ref`` at the session's read length — the tuner needs realistic
    *shapes*, not realistic biology.  Returns the (merged) entries dict;
    with ``save`` (default) it is written to `cache_path(path)` so a
    subsequent ``Mapper.build(..., ExecutionConfig(tune=...))`` picks the
    winners up.
    """
    from repro.core import ReadSimConfig, simulate_pairs
    from repro.core.seedmap import PaddedSeedMap, to_padded
    from repro.engine.config import ExecutionConfig, resolved_pipeline

    exec_cfg = exec_cfg or ExecutionConfig()
    cfg = resolved_pipeline(pipe_cfg or PipelineConfig(), exec_cfg)
    lr_cfg = lr_cfg or LongReadConfig(
        pipe=dataclasses.replace(cfg, packed_ref=None))
    backends = _family_backends(pipe_cfg or PipelineConfig(),
                                exec_cfg.backend)
    vote_backend = resolve_backend(exec_cfg.backend or lr_cfg.vote_backend,
                                   family="location_vote")
    buckets = pipeline_buckets(cfg, batch, lr_cfg)

    ref_np = np.asarray(ref, dtype=np.uint8)
    ref_j = jnp.asarray(ref_np)
    sim = simulate_pairs(ref_np, batch,
                         ReadSimConfig(read_len=cfg.read_len), seed=seed)
    reads1 = jnp.asarray(sim.reads1)
    reads2_fwd = (3 - jnp.asarray(sim.reads2))[:, ::-1]
    padded = (sm if isinstance(sm, PaddedSeedMap)
              else to_padded(sm, cap=cfg.max_locs_per_seed))
    rng = np.random.default_rng(seed + 1)
    meta = {"batch": batch, "reps": reps,
            "platform": jax.default_backend(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}
    entries = load_cache(path) if save else {}

    def record(family, backend, timed, staged_label):
        params, us, staged_us = _winner(timed, staged_label)
        entries[entry_key(backend, family, buckets[family])] = {
            "params": params, "us": round(us, 2),
            "staged_us": round(staged_us, 2), "meta": dict(meta)}

    # ---- pair_frontend --------------------------------------------------
    if "pair_frontend" in families:
        from repro.kernels.pair_frontend.ops import pair_frontend

        bk = backends["pair_frontend"]
        cands = [("staged", {"backend": "jnp"},
                  lambda: pair_frontend(
                      padded.rows, reads1, reads2_fwd, cfg.seed_len,
                      cfg.seeds_per_read, sm.config.hash_seed, cfg.delta,
                      cfg.max_candidates, backend="jnp"))]
        if bk != "jnp":
            for b in BLOCK_GRID["pair_frontend"]:
                cands.append((
                    f"block{b}", {"block": b},
                    lambda b=b: pair_frontend(
                        padded.rows, reads1, reads2_fwd, cfg.seed_len,
                        cfg.seeds_per_read, sm.config.hash_seed,
                        cfg.delta, cfg.max_candidates, block=b,
                        backend=bk)))
        record("pair_frontend", bk, _time_candidates(cands, reps),
               "staged")

    # ---- candidate_align ------------------------------------------------
    if "candidate_align" in families:
        from repro.core.encoding import pack_2bit
        from repro.kernels.candidate_align.ops import candidate_pair_align
        from repro.kernels.pair_frontend.ops import pair_frontend as _fe

        # The frontend's real candidate set feeds the align sweep.
        fe = _fe(padded.rows, reads1, reads2_fwd, cfg.seed_len,
                 cfg.seeds_per_read, sm.config.hash_seed, cfg.delta,
                 cfg.max_candidates, backend="jnp")

        bk = backends["candidate_align"]
        words = jnp.asarray(pack_2bit(ref_np))
        C = cfg.max_candidates

        def la(block=None, ps=0, packed=False, backend=bk):
            return candidate_pair_align(
                words if packed else ref_j, reads1, reads2_fwd,
                fe.pos1, fe.pos2, cfg.max_gap, scoring=cfg.scoring,
                threshold=cfg.threshold(), mode=cfg.light_mode,
                prescreen_top=ps, packed_ref=packed, block=block,
                backend=backend)

        cands = []
        ps_grid = sorted({0, max(1, C // 2)})
        for ps in ps_grid:
            for packed in (False, True):
                cands.append((
                    f"staged_ps{ps}_pk{int(packed)}",
                    {"backend": "jnp", "prescreen_top": ps,
                     "packed_ref": packed},
                    lambda ps=ps, packed=packed: la(
                        ps=ps, packed=packed, backend="jnp")))
        if bk != "jnp":
            for b in BLOCK_GRID["candidate_align"]:
                for ps in ps_grid:
                    for packed in (False, True):
                        cands.append((
                            f"block{b}_ps{ps}_pk{int(packed)}",
                            {"block": b, "prescreen_top": ps,
                             "packed_ref": packed},
                            lambda b=b, ps=ps, packed=packed: la(
                                block=b, ps=ps, packed=packed)))
        record("candidate_align", bk, _time_candidates(cands, reps),
               "staged_ps0_pk0")

    # ---- residual_dp ----------------------------------------------------
    if "residual_dp" in families:
        from repro.kernels.residual_dp.ops import residual_pair_dp

        bk = backends["residual_dp"]
        cap = max(1, cfg.residual_cap(batch))
        L = int(ref_np.shape[0])
        W = cfg.read_len + 2 * cfg.dp_pad
        p1 = jnp.asarray(rng.integers(
            cfg.dp_pad, max(cfg.dp_pad + 1, L - W), (cap,)).astype(np.int32))
        p2 = jnp.asarray(rng.integers(
            cfg.dp_pad, max(cfg.dp_pad + 1, L - W), (cap,)).astype(np.int32))
        # Typical residual mix: mostly a single failed mate per row.
        n1 = jnp.asarray(rng.random(cap) < 0.55)
        n2 = jnp.asarray(np.where(np.asarray(n1), rng.random(cap) < 0.15,
                                  True))
        r1, r2 = reads1[:cap], reads2_fwd[:cap]

        def dp(block=None, band=None, backend=bk):
            return residual_pair_dp(
                ref_j, r1, r2, p1, p2, n1, n2, cfg.dp_pad,
                band=cfg.band() if band is None else band,
                scoring=cfg.scoring, block=block, backend=backend)

        band_grid = [(None, cfg.band()), ("full", W)]
        cands = [("staged", {"backend": "jnp"},
                  lambda: dp(backend="jnp"))]
        if bk != "jnp":
            for b in BLOCK_GRID["residual_dp"]:
                for tag, band in band_grid:
                    params = {"block": b}
                    if tag == "full":
                        params["dp_band"] = band
                    cands.append((
                        f"block{b}_band{band}", params,
                        lambda b=b, band=band: dp(block=b, band=band)))
        record("residual_dp", bk, _time_candidates(cands, reps), "staged")

    # ---- location_vote --------------------------------------------------
    if "location_vote" in families:
        from repro.core.seedmap import INVALID_LOC
        from repro.kernels.location_vote.ops import location_vote

        S = lr_cfg.n_segments(3000)
        M = max(1, (S - 1)) * cfg.max_candidates
        diag_np = rng.integers(0, max(2, len(ref_np) - 256),
                               (batch, M)).astype(np.int32)
        diag_np[rng.random((batch, M)) < 0.5] = INVALID_LOC
        diag = jnp.asarray(diag_np)

        cands = [("staged", {"backend": "jnp"},
                  lambda: location_vote(diag, lr_cfg.vote_bin,
                                        backend="jnp"))]
        if vote_backend != "jnp":
            for b in BLOCK_GRID["location_vote"]:
                cands.append((
                    f"block{b}", {"block": b},
                    lambda b=b: location_vote(diag, lr_cfg.vote_bin,
                                              block=b,
                                              backend=vote_backend)))
        record("location_vote", vote_backend,
               _time_candidates(cands, reps), "staged")

    if save:
        save_cache(entries, path)
    return entries


# ---------------------------------------------------------------- CLI --
def main(argv=None) -> None:
    from repro.core import SeedMapConfig, build_seedmap, random_reference

    ap = argparse.ArgumentParser(
        description="Autotune fused-kernel configs; write the tune cache.")
    ap.add_argument("--ref-len", type=int, default=300_000)
    ap.add_argument("--table-bits", type=int, default=19)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--families", default=",".join(FAMILIES),
                    help="comma-separated subset of " + ",".join(FAMILIES))
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default {DEFAULT_CACHE}; "
                         f"${ENV_CACHE} honored)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    rng = np.random.default_rng(0)
    ref = random_reference(args.ref_len, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=args.table_bits))
    entries = tune_session(
        ref, sm, batch=args.batch, reps=args.reps,
        families=tuple(args.families.split(",")), path=args.cache)
    print(f"wrote {cache_path(args.cache)} ({len(entries)} entries)")
    for k in sorted(entries):
        e = entries[k]
        print(f"  {k}: {e['params']} us={e['us']} "
              f"staged_us={e['staged_us']}")


if __name__ == "__main__":
    main()
