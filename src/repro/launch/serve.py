"""Genomics serving driver: batched paired-end read mapping (the paper's
workload kind).

Offline stage: build the reference + SeedMap index and a `repro.engine`
`Mapper` session (backends, reference flavor and SeedMap layout resolved
once).  Online stage: stream fixed-size batches of FR read pairs through
``mapper.map_stream`` — the async double-buffered host loop that overlaps
read simulation and H2D with the in-flight step, accumulates StageStats
(Fig. 10) *and* the accuracy counters on device, and syncs the host
exactly once at the end.  Accuracy is validated per mate (``pos1`` vs
``true_start1`` and ``pos2`` vs ``true_start2``) and at pair level.

``--workload long`` serves the long-read lane instead: `serve_long`
streams simulated PacBio-like batches through ``mapper.map_long_stream``
with a device-side vote-accuracy reduction.

``--loop frontdoor`` serves a synthetic *bursty ragged-arrival* trace —
requests of 1..batch read pairs or long reads, both lanes interleaved —
through the continuous-batching front door (`repro.engine.frontdoor`):
queue coalescing, admission control and the per-request latency ledger,
reported next to throughput in the output JSON.

Usage (CPU):
  PYTHONPATH=src python -m repro.launch.serve --ref-len 500000 \
      --batches 10 --batch 512
  PYTHONPATH=src python -m repro.launch.serve --workload long \
      --batch 64 --batches 5
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax.numpy as jnp
import numpy as np

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_long_reads, simulate_pairs,
)
from repro.compile_cache import enable_compile_cache
from repro.core.seedmap import INVALID_LOC
from repro.data.pipeline import ReadStreamConfig, read_pairs_for_step
from repro.engine import ExecutionConfig, LongReadConfig, Mapper, spans

ACC_KEYS = ("mapped1", "mapped2", "correct1", "correct2",
            "pair_mapped", "pair_correct")

@functools.lru_cache(maxsize=None)
def _make_accuracy_reduce(max_gap: int):
    """Device-side per-batch accuracy reduction (both mates + pair).

    The pre-engine loop validated only mate 1; this scores ``pos2``
    against ``true_start2`` too, plus pair-level correctness (both mates
    mapped / both within ``max_gap``).  Traced into `map_stream`'s fused
    per-batch dispatch, so it costs no extra host work or sync; padded
    tail rows are excluded via ``res.n_valid``.
    """

    def reduce(acc, res, aux):
        t1, t2 = aux
        v = res.n_valid
        m1 = (res.pos1 != INVALID_LOC) & v
        m2 = (res.pos2 != INVALID_LOC) & v
        c1 = m1 & (jnp.abs(res.pos1 - t1) <= max_gap)
        c2 = m2 & (jnp.abs(res.pos2 - t2) <= max_gap)
        new = {
            "mapped1": m1, "mapped2": m2, "correct1": c1, "correct2": c2,
            "pair_mapped": m1 & m2, "pair_correct": c1 & c2,
        }
        return {k: acc[k] + jnp.sum(new[k].astype(jnp.int32))
                for k in ACC_KEYS}

    return reduce


@functools.lru_cache(maxsize=None)
def _make_vote_accuracy_reduce(vote_bin: int):
    """Device-side long-read accuracy reduction (mapped / vote-correct).

    Cached like `_make_accuracy_reduce` so repeated `serve_long` calls
    hand `map_long_stream` the *same* callable — the Mapper's fused-step
    cache keys on ``(lane, reduce_fn)``, and a fresh closure per call
    would recompile every stream.
    """

    def reduce(acc, res, aux):
        (true,) = aux
        m = res.mapped & res.n_valid
        c = m & (jnp.abs(res.position - true) <= vote_bin)
        return {"mapped": acc["mapped"] + jnp.sum(m.astype(jnp.int32)),
                "correct": acc["correct"] + jnp.sum(c.astype(jnp.int32))}

    return reduce


def _session_from_store(index_path, ref, table_bits, pipe_cfg, exec_cfg,
                        ) -> tuple[Mapper, float]:
    """Cold-start a serve session from a saved index store.

    Returns ``(mapper, seconds_to_ready)``.  An unreadable store warns
    and degrades to a full ``Mapper.build`` on the driver's reference —
    the worker comes up either way (`Mapper.load`'s fallback contract).
    """
    t0 = time.time()
    mapper = Mapper.load(index_path, exec_cfg, fallback_ref=ref,
                         seedmap_cfg=SeedMapConfig(table_bits=table_bits),
                         pipe_cfg=pipe_cfg)
    return mapper, time.time() - t0


def serve(ref_len: int = 500_000, batch: int = 512, batches: int = 10,
          table_bits: int = 20, sub_rate: float = 1e-3,
          pipe_cfg: PipelineConfig = PipelineConfig(),
          seed: int = 0, verbose: bool = True,
          index_path: str | None = None,
          chaos: str | None = None) -> dict:
    rng = np.random.default_rng(seed)
    t0 = time.time()
    ref = random_reference(ref_len, rng)
    mapper = sm = None
    if index_path is not None:
        mapper, t_index = _session_from_store(
            index_path, ref, table_bits, pipe_cfg,
            ExecutionConfig(stream_batch=batch))
    else:
        sm = build_seedmap(ref, SeedMapConfig(table_bits=table_bits))
        t_index = time.time() - t0

    stream = ReadStreamConfig(batch=batch, read_len=pipe_cfg.read_len,
                              seed=seed)
    sim_cfg = ReadSimConfig(read_len=pipe_cfg.read_len, sub_rate=sub_rate)

    out = _serve_stream(ref, sm, stream, sim_cfg, batch, batches, pipe_cfg,
                        t_index, mapper=mapper, chaos=chaos)
    # The program's span table and step-trace counts (engine.spans):
    # where the host time went, and whether a step recompiled.
    out["spans"] = spans.snapshot()
    if verbose:
        print(json.dumps(out, indent=1), flush=True)
    return out


def _serve_stream(ref, sm, stream, sim_cfg, batch, batches, pipe_cfg,
                  t_index, mapper: Mapper | None = None,
                  chaos: str | None = None) -> dict:
    if mapper is None:
        mapper = Mapper.from_index(
            sm, ref, pipe_cfg, ExecutionConfig(stream_batch=batch))

    def gen():
        for step in range(batches):
            sim = read_pairs_for_step(ref, stream, step, sim_cfg)
            yield sim.reads1, sim.reads2, (sim.true_start1, sim.true_start2)

    # warmup/compile on batch 0
    sim0 = read_pairs_for_step(ref, stream, 0, sim_cfg)
    warmup = (sim0.reads1, sim0.reads2,
              (sim0.true_start1, sim0.true_start2))
    reduce_kw = dict(
        reduce_fn=_make_accuracy_reduce(pipe_cfg.max_gap),
        reduce_init={k: jnp.zeros((), jnp.int32) for k in ACC_KEYS})
    health = None
    if chaos is not None:
        # Fault-tolerant path: the batch source is wrapped with the
        # deterministic fault schedule and served through the fleet
        # stream (`engine.multihost.map_stream` — on one host the
        # keep-alive protocol is bypassed, but SIGTERM still drains
        # between batches and the watchdog tracks generator stalls).
        from repro.engine import multihost
        from repro.runtime import ChaosSpec, PreemptionGuard, inject
        from repro.runtime.watchdog import STRAGGLE_DEMO_WATCHDOG

        spec = ChaosSpec.parse(chaos)
        guard = PreemptionGuard()
        try:
            sr = multihost.map_stream(
                mapper,
                inject(gen(), spec, host=multihost.process_index()),
                guard=guard,
                watchdog=STRAGGLE_DEMO_WATCHDOG
                if any(f.kind == "straggle" for f in spec.faults)
                else None,
                warmup_batch=warmup, **reduce_kw)
        finally:
            guard.uninstall()
        health = sr.health
    else:
        sr = mapper.map_stream(gen(), warmup_batch=warmup, **reduce_kw)
    a = {k: int(v) for k, v in sr.reduced.items()}
    n = max(sr.n_pairs, 1)
    if health is not None:
        return {
            "pairs": sr.n_pairs,
            "pairs_per_s": sr.pairs_per_s,
            "index_build_s": t_index,
            "loop": "stream",
            "chaos": chaos,
            "health": health,
            "mapped_frac": a["mapped1"] / n,
            "correct_of_mapped": a["correct1"] / max(a["mapped1"], 1),
            **sr.fractions,
        }
    return {
        "pairs": sr.n_pairs,
        "pairs_per_s": sr.pairs_per_s,
        "mbp_per_s": sr.mbp_per_s(pipe_cfg.read_len),
        "index_build_s": t_index,
        "loop": "stream",
        # mate-1 keys keep their historical names; mate-2 and pair-level
        # correctness are the serve accuracy-check fix.
        "mapped_frac": a["mapped1"] / n,
        "correct_of_mapped": a["correct1"] / max(a["mapped1"], 1),
        "mapped_frac2": a["mapped2"] / n,
        "correct_of_mapped2": a["correct2"] / max(a["mapped2"], 1),
        "pair_mapped_frac": a["pair_mapped"] / n,
        "pair_correct_of_mapped": a["pair_correct"] / max(a["pair_mapped"],
                                                          1),
        **sr.fractions,
    }


def serve_long(ref_len: int = 500_000, batch: int = 64, batches: int = 10,
               table_bits: int = 20, read_len: int = 4500,
               sub_rate: float = 0.01,
               lr_cfg: LongReadConfig = LongReadConfig(),
               seed: int = 0, verbose: bool = True,
               index_path: str | None = None) -> dict:
    """The long-read serve workload (``--workload long``).

    Same shape as the pair loop: offline index + session build (the
    long-read lane resolves at `Mapper` build), then `map_long_stream`
    over simulated PacBio-like batches with a device-side accuracy
    reduction (mapped / voted position within one vote bin of truth) —
    one fused dispatch per batch, one host sync at the end.
    """
    rng = np.random.default_rng(seed)
    t0 = time.time()
    ref = random_reference(ref_len, rng)
    exec_cfg = ExecutionConfig(stream_batch=batch, long_read=lr_cfg)
    if index_path is not None:
        mapper, t_index = _session_from_store(index_path, ref, table_bits,
                                              lr_cfg.pipe, exec_cfg)
    else:
        sm = build_seedmap(ref, SeedMapConfig(table_bits=table_bits))
        t_index = time.time() - t0
        mapper = Mapper.from_index(sm, ref, lr_cfg.pipe, exec_cfg)
    bin_ = mapper.lr_cfg.vote_bin

    def gen():
        for step in range(batches):
            reads, starts = simulate_long_reads(
                ref, batch, read_len, sub_rate, seed=seed + 1 + step)
            yield reads, (jnp.asarray(starts),)

    w_reads, w_starts = simulate_long_reads(ref, batch, read_len, sub_rate,
                                            seed=seed)
    sr = mapper.map_long_stream(
        gen(), reduce_fn=_make_vote_accuracy_reduce(bin_),
        reduce_init={"mapped": jnp.zeros((), jnp.int32),
                     "correct": jnp.zeros((), jnp.int32)},
        warmup_batch=(w_reads, (jnp.asarray(w_starts),)))
    a = {k: int(v) for k, v in sr.reduced.items()}
    out = {
        "reads": sr.n_pairs,
        "reads_per_s": sr.pairs_per_s,
        # StreamResult knows the lane's bases-per-item factor
        # (reads_per_item=1 on the long lane), so no inline recompute.
        "mbp_per_s": sr.mbp_per_s(read_len),
        "index_build_s": t_index,
        "loop": "stream",
        "workload": "long",
        "mapped_frac": a["mapped"] / max(sr.n_pairs, 1),
        "correct_of_mapped": a["correct"] / max(a["mapped"], 1),
        **sr.fractions,
    }
    out["spans"] = spans.snapshot()
    if verbose:
        print(json.dumps(out, indent=1), flush=True)
    return out


def serve_frontdoor(ref_len: int = 500_000, batch: int = 256,
                    batches: int = 10, table_bits: int = 20,
                    sub_rate: float = 1e-3, long_sub_rate: float = 0.01,
                    read_len: int = 2000, long_frac: float = 0.2,
                    max_queue_rows: int | None = None,
                    deadline_s: float | None = None,
                    pipe_cfg: PipelineConfig = PipelineConfig(),
                    seed: int = 0, verbose: bool = True,
                    index_path: str | None = None) -> dict:
    """Bursty ragged-arrival serving through the continuous-batching
    front door (``--loop frontdoor``).

    Synthesizes a request trace the paper's target traffic looks like —
    ragged sizes (1..batch read pairs or long reads per request), the
    short-read and long-read lanes interleaved — and drives it through
    `engine.frontdoor.FrontDoor` on one `Mapper` session (`frontdoor_trace`):
    coalescing into fixed-shape device batches, admission control,
    per-request latency ledger, starvation-free two-lane scheduling.  The
    output JSON reports throughput per lane next to the queue-latency
    percentiles and the shed/reject accounting.
    """
    rng = np.random.default_rng(seed)
    t0 = time.time()
    ref = random_reference(ref_len, rng)
    if index_path is not None:
        mapper, t_index = _session_from_store(
            index_path, ref, table_bits, pipe_cfg,
            ExecutionConfig(stream_batch=batch))
    else:
        sm = build_seedmap(ref, SeedMapConfig(table_bits=table_bits))
        t_index = time.time() - t0
        mapper = Mapper.from_index(sm, ref, pipe_cfg,
                                   ExecutionConfig(stream_batch=batch))
    out = {"loop": "frontdoor", "index_build_s": t_index,
           **frontdoor_trace(mapper, ref, batch, batches, sub_rate=sub_rate,
                             long_sub_rate=long_sub_rate, read_len=read_len,
                             long_frac=long_frac,
                             max_queue_rows=max_queue_rows,
                             deadline_s=deadline_s, rng=rng, seed=seed)}
    out["spans"] = spans.snapshot()
    if verbose:
        print(json.dumps(out, indent=1), flush=True)
    return out


def frontdoor_trace(mapper: Mapper, ref, batch: int, batches: int, *,
                    sub_rate: float = 1e-3, long_sub_rate: float = 0.01,
                    read_len: int = 2000, long_frac: float = 0.2,
                    max_queue_rows: int | None = None,
                    deadline_s: float | None = None, rng=None,
                    seed: int = 0) -> dict:
    """Serve one bursty ragged two-lane trace through a `FrontDoor` on an
    existing session: ``batch * batches`` read pairs plus ``long_frac``
    as many ``read_len`` long reads, in requests of 1..``batch`` rows.
    Returns throughput, the serving ledger (``requests`` counts the
    arrivals) and the per-lane stage totals.
    """
    from repro.engine import FrontDoor, FrontDoorConfig

    if rng is None:
        rng = np.random.default_rng(seed)
    read_len_pairs = mapper.pipe_cfg.read_len
    # Request pools are simulated up front so arrivals pay no host-side
    # generation inside the latency-stamped serve window.
    n_pair_rows = batch * batches
    sim = simulate_pairs(
        ref, n_pair_rows,
        ReadSimConfig(read_len=read_len_pairs, sub_rate=sub_rate),
        seed=seed)
    n_long_rows = int(round(n_pair_rows * long_frac)) if long_frac > 0 else 0
    if n_long_rows:
        long_reads, _ = simulate_long_reads(ref, n_long_rows, read_len,
                                            long_sub_rate, seed=seed + 1)
    n_requests = 0

    def arrivals():
        """Ragged bursty trace: mixed small/large requests, lanes
        interleaved, until both pools are spent."""
        nonlocal n_requests
        pair_off = long_off = 0
        while pair_off < n_pair_rows or long_off < n_long_rows:
            n_requests += 1
            go_long = (long_off < n_long_rows
                       and (pair_off >= n_pair_rows
                            or rng.random() < long_frac))
            # bursty size mix: mostly small requests, occasional
            # near-batch bursts
            hi = batch if rng.random() < 0.25 else max(2, batch // 8)
            n = int(rng.integers(1, hi + 1))
            if go_long:
                n = min(n, n_long_rows - long_off)
                yield ("long", (long_reads[long_off:long_off + n],))
                long_off += n
            else:
                n = min(n, n_pair_rows - pair_off)
                yield ("pairs", (sim.reads1[pair_off:pair_off + n],
                                 sim.reads2[pair_off:pair_off + n]))
                pair_off += n

    fd = FrontDoor(mapper, FrontDoorConfig(
        max_queue_rows=max_queue_rows, default_deadline_s=deadline_s))
    try:
        fd.warmup(long_reads=long_reads[:1] if n_long_rows else None)
        t1 = time.time()
        report = fd.serve(arrivals())
        seconds = time.time() - t1
    finally:
        fd.close()

    pair_rows = report["stage_totals"]["pairs"]["n_pairs"]
    long_rows = report["stage_totals"].get("long", {}).get("n_reads", 0)
    return {
        "seconds": seconds,
        "pairs": pair_rows,
        "long_reads": long_rows,
        "pairs_per_s": pair_rows / max(seconds, 1e-9),
        "mbp_per_s": (pair_rows * 2 * read_len_pairs
                      + long_rows * read_len) / max(seconds, 1e-9) / 1e6,
        "requests": n_requests,
        **report["serve"],
        "stage_totals": report["stage_totals"],
        "watchdog": report["watchdog"],
    }


def save_index(path: str, ref_len: int = 500_000, batch: int = 512,
               table_bits: int = 20, sub_rate: float = 1e-3,
               pipe_cfg: PipelineConfig = PipelineConfig(),
               seed: int = 0, verbose: bool = True, **_ignored) -> dict:
    """``--save-index``: build the session once and persist its store.

    The store carries the *resolved* session (index layout, reference
    flavor, configs), so a later ``--index`` serve of the same shapes
    cold-starts without `build_seedmap` and maps bit-identically.
    """
    from repro.engine.index_store import store_size_bytes

    rng = np.random.default_rng(seed)
    t0 = time.time()
    ref = random_reference(ref_len, rng)
    mapper = Mapper.build(ref, SeedMapConfig(table_bits=table_bits),
                          pipe_cfg, ExecutionConfig(stream_batch=batch))
    t_build = time.time() - t0
    t0 = time.time()
    manifest = mapper.save(path)
    out = {
        "store": path,
        "manifest": manifest,
        "index_build_s": t_build,
        "save_s": time.time() - t0,
        "store_mb": store_size_bytes(path) / 1e6,
        "layout": type(mapper.index).__name__,
    }
    out["spans"] = spans.snapshot()
    if verbose:
        print(json.dumps(out, indent=1), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref-len", type=int, default=500_000)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--table-bits", type=int, default=20)
    ap.add_argument("--sub-rate", type=float, default=None,
                    help="substitution rate; defaults per workload "
                         "(1e-3 short pairs, PacBio-like 0.01 long)")
    ap.add_argument("--loop", choices=("stream", "frontdoor"),
                    default="stream",
                    help="host loop: pre-batched map_stream (default) or "
                         "the continuous-batching front door (bursty "
                         "ragged arrivals, two lanes interleaved)")
    ap.add_argument("--workload", choices=("pairs", "long"),
                    default="pairs",
                    help="short FR pairs (default) or the long-read lane")
    ap.add_argument("--read-len", type=int, default=4500,
                    help="long-read length (bp): --workload long and the "
                         "frontdoor long lane")
    ap.add_argument("--long-frac", type=float, default=0.2,
                    help="--loop frontdoor: fraction of request traffic "
                         "on the long-read lane")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="--loop frontdoor: per-request deadline")
    ap.add_argument("--max-queue-rows", type=int, default=None,
                    help="--loop frontdoor: admission-control queue bound")
    ap.add_argument("--out", default=None,
                    help="write the result JSON here")
    ap.add_argument("--save-index", default=None, metavar="PATH",
                    help="build the index + session, persist the store "
                         "to PATH (engine.index_store) and exit")
    ap.add_argument("--index", default=None, metavar="PATH",
                    help="serve from a saved index store instead of "
                         "rebuilding (composes with --loop frontdoor and "
                         "--workload long; unreadable stores degrade to "
                         "a full build)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection on the stream "
                         "loop (runtime.faultinject grammar, e.g. "
                         "'dry@0:3' or 'sigterm@0:2,straggle@0:1:0.05'): "
                         "the serve drains instead of crashing and the "
                         "output carries the health ledger")
    ap.add_argument("--health-out", default=None, metavar="PATH",
                    help="write the --chaos health ledger JSON here "
                         "(the CI fleet artifact)")
    args = ap.parse_args()
    enable_compile_cache()
    # The shared flag must not clobber per-workload defaults: short pairs
    # default 1e-3, the long lane the PacBio-like 0.01.
    sub_rate = args.sub_rate
    if sub_rate is None:
        sub_rate = 0.01 if args.workload == "long" else 1e-3
    kwargs = dict(ref_len=args.ref_len, batch=args.batch,
                  batches=args.batches, table_bits=args.table_bits,
                  sub_rate=sub_rate)
    if args.save_index:
        out = save_index(args.save_index, **kwargs)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)
        return
    if args.loop == "frontdoor":
        if args.chaos:
            raise SystemExit("--chaos composes with --loop stream; the "
                             "front door has its own guard/watchdog path")
        out = serve_frontdoor(read_len=args.read_len,
                              long_frac=args.long_frac,
                              deadline_s=args.deadline_s,
                              max_queue_rows=args.max_queue_rows,
                              index_path=args.index,
                              **kwargs)
    elif args.workload == "long":
        if args.chaos:
            raise SystemExit("--chaos currently drives the pairs stream "
                             "loop only")
        out = serve_long(read_len=args.read_len, index_path=args.index,
                         **kwargs)
    else:
        out = serve(index_path=args.index, chaos=args.chaos, **kwargs)
    if args.health_out and out.get("health") is not None:
        os.makedirs(os.path.dirname(args.health_out) or ".", exist_ok=True)
        with open(args.health_out, "w") as f:
            json.dump(out["health"], f, indent=2, sort_keys=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
