"""One run of one cell: set-up, the measured window, the correctness
check against the plain reference, and the result line.

Set-up (`setup_s`, from process start to the first timed dispatch):
generate the genome from the configuration's ``ref_seed``, open the
session from the index store, generate the read pool from ``--seed``
and warm the cell's one stream shape.  The window then drives
``map_stream`` (pairs) or ``map_long_stream`` (long reads) closed loop
over the pool until ``--seconds`` have passed, and ends when the last
batch is ready.  A ``--trace 1`` run traces a window of at most
`TRACE_SECONDS` and reports the per-layer metrics instead.

After the window a sample of its batches, drawn from the seed, is
compared field by field with the plain reference (`reference.py`), once
the program's session has been freed.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import time
import types
from pathlib import Path

import numpy as np

from chipbench import reference, session
from chipbench.cell import load_cell, load_peaks, load_reader
from chipbench.readgen import make_genome, make_pool

TRACE_SECONDS = 3.0
#: window batches compared with the reference, drawn from the seed
CHECK_BATCHES = 2
#: rows of the sampled batches allowed to differ from the reference
ROWS_DIFFERING_LIMIT = 0


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def check_device(devices, chips: int) -> dict:
    """The result's device record; refuses anything but a TPU with at
    least ``chips`` devices."""
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {d0.platform!r}); "
                         "the benchmark never runs on another backend")
    if len(devices) < chips:
        raise SystemExit(f"{chips} chips requested, {len(devices)} found")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


class CompileCounter:
    """Backend compilations and persistent-cache events, as JAX reports
    them; ``in_window`` counts compilations while `window` is open."""

    def __init__(self):
        from jax import monitoring

        self.cache = {"hits": 0, "misses": 0}
        self.in_window = 0
        self._open = False
        names = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}

        def on_event(event, **_):
            if event in names:
                self.cache[names[event]] += 1

        def on_duration(event, _secs, **_):
            if self._open and event.endswith("backend_compile_duration"):
                self.in_window += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    @contextlib.contextmanager
    def window(self):
        self._open = True
        try:
            yield
        finally:
            self._open = False


class Sample:
    """A uniform sample of `CHECK_BATCHES` window results, drawn from the
    seed (reservoir sampling), kept on the device until the window ends."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        self.kept: list = []

    def offer(self, idx, res, _n):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.on_result"):
            if len(self.kept) < CHECK_BATCHES:
                self.kept.append((idx, res))
            else:
                j = int(self.rng.integers(0, idx + 1))
                if j < CHECK_BATCHES:
                    self.kept[j] = (idx, res)


def feed(items: list, seconds: float):
    """Cycle the pool until ``seconds`` after the first batch."""
    from jax.profiler import TraceAnnotation

    t_end = None
    i = 0
    while t_end is None or time.perf_counter() < t_end:
        with TraceAnnotation("bench.feed"):
            item = items[i % len(items)]
        if t_end is None:
            t_end = time.perf_counter() + seconds
        yield item
        i += 1


def rows_differing(got: dict, want: dict) -> tuple[int, dict]:
    """Rows with any field off the reference, and the count per field."""
    bad = np.zeros(next(iter(want.values())).shape[0], bool)
    per_field = {}
    for field, w in want.items():
        g = np.asarray(got[field])
        diff = (g != w).reshape(w.shape[0], -1).any(axis=1)
        per_field[field] = int(diff.sum())
        bad |= diff
    return int(bad.sum()), per_field


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, control: bool = False, require_tpu: bool = True,
             t_start: float | None = None) -> dict:
    """One run; returns the result line's object (also printed)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if require_tpu:
        device = check_device(devices, cell.chips)
    else:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    log("device", cache_dir=cache_dir, control=control, **device)

    g = cell.config["genome"]
    t0 = time.perf_counter()
    genome = make_genome(g["length"], g["ref_seed"], g["repeat_frac"],
                         g["n_families"], g["family_len"], g["divergence"])
    log("genome", length=genome.shape[0], seconds=time.perf_counter() - t0)

    mapper, info = session.open_session(cell, genome, control=control)
    log("session", backends=session.backends(mapper), **info)

    t0 = time.perf_counter()
    pool = make_pool(genome, cell.lane, cell.batch, cell.traffic, seed)
    items = [session.stream_item(cell, b) for b in pool]
    log("pool", batches=len(pool), batch=cell.batch,
        items=len(pool) * cell.batch, seconds=time.perf_counter() - t0,
        mean_edits_per_read=float(np.mean([b["edits"].mean()
                                            for b in pool])))

    reduce_fn, reduce_init = session.accuracy_reduce(cell, mapper)
    t0 = time.perf_counter()
    session.stream(cell, mapper, iter(items[:1]), warmup_batch=items[0],
                   reduce_fn=reduce_fn, reduce_init=reduce_init)
    log("warm", seconds=time.perf_counter() - t0, **compiles.cache)

    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    trace_dir = cell.bench_dir / "out" / "trace"
    sample = Sample(seed)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - t_start
    with compiles.window(), jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        sr = session.stream(cell, mapper, feed(items, window_s),
                            on_result=sample.offer, reduce_fn=reduce_fn,
                            reduce_init=reduce_init)
        elapsed = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:cell.chips])
    mbp_per_s = sr.n_pairs * cell.bases_per_item() / elapsed / 1e6
    log("window", seconds=elapsed, batches=sr.n_batches, items=sr.n_pairs,
        mbp_per_s=mbp_per_s, compiles_in_window=compiles.in_window,
        memory_peak_bytes=memory_peak)
    log("stage_totals", **sr.totals)
    acc = {k: int(v) for k, v in sr.reduced.items()}
    log("accuracy_vs_truth", items=sr.n_pairs,
        **{k: v / max(sr.n_pairs, 1) for k, v in acc.items()})
    log("compile_cache", **compiles.cache)

    # -- correctness: the sampled window batches against the reference --
    got = [(idx, {f: np.asarray(getattr(res, f)) for f in res._fields})
           for idx, res in sample.kept]
    totals, n_batches = sr.totals, sr.n_batches
    del mapper, sr, sample
    gc.collect()
    t0 = time.perf_counter()
    want = reference.map_batches(jnp.asarray(genome),
                                 [pool[idx % len(pool)] for idx, _ in got],
                                 cell.reference_params(), cell.lane)
    differing, per_field = 0, {}
    for (idx, g_res), w_res in zip(got, want):
        n, fields = rows_differing(g_res, w_res)
        differing += n
        for f, c in fields.items():
            per_field[f] = per_field.get(f, 0) + c
    compared = sum(w["n_valid"].shape[0] for w in want)
    log("reference", batches=[idx for idx, _ in got], rows=compared,
        rows_differing=differing, per_field=per_field,
        seconds=time.perf_counter() - t0)
    checks = {"rows_differing": {"value": differing,
                                 "limit": ROWS_DIFFERING_LIMIT}}
    correct = differing <= ROWS_DIFFERING_LIMIT

    device["memory_peak_bytes"] = int(memory_peak)
    result = {"correct": bool(correct), "attempted": int(totals.get(
        "n_pairs", totals.get("n_reads", 0))), "failed": differing}
    if trace:
        from chipbench.tracing import read_trace

        summary = read_trace(str(trace_dir))
        run = types.SimpleNamespace(
            cell=cell, trace=summary, totals=totals, n_batches=n_batches,
            peaks=load_peaks(cell, device["kind"]),
            memory_peak_bytes=memory_peak)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps()}
    else:
        values = {"mbp_per_s": mbp_per_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result
