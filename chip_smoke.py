"""Chip smoke test: the paired-end mapping path on a TPU, end to end.

    python chip_smoke.py             # one chip (every phase below)
    python chip_smoke.py --chips 4   # the sharded-index plan on four chips

Run from the root of a checkout; the script puts ``src`` on ``sys.path``
itself.  One chip drives the normal entry points at deployment size:

  1. a `Mapper` session over a 248,956,422-base reference (the length of
     GRCh38 chr1) generated from ``--seed``, indexed with a 2^25-bucket
     SeedMap padded to K=32, default `PipelineConfig`; every kernel family
     must resolve to its Pallas kernel;
  2. ``map_stream`` over 8 batches of 4096 simulated pairs with the
     serve CLI's device-side accuracy reduction;
  3. bit-identity of every `MapResult` field against a session on the
     staged jnp oracle, on 512 pairs;
  4. a ragged two-lane `FrontDoor` trace (short pairs and long reads),
     every request answered, none rejected.

``--chips 4`` runs only the sharded-index plan on a (1, 4) mesh: it checks
that each CSR shard sits on its own chip and that the plan's results are
bit-identical to a single-device session on the same pairs.

Throughput lines are information only.  The script exits non-zero, and
never prints the final line, when JAX finds no TPU (it does not fall back
to the CPU) or when any phase fails.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CHR1_LEN = 248_956_422     # GRCh38 chromosome 1
TABLE_BITS = 25            # 2^25 buckets x K=32: 4 GiB of padded rows
STREAM_BATCH = 4096
STREAM_BATCHES = 8
ORACLE_PAIRS = 512


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


class SmokeFailure(SystemExit):
    """A failed phase: exits non-zero with the reason on stderr."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def check_device(devices, chips: int = 1) -> dict:
    """The device record of the final line; refuses anything but a TPU
    with at least ``chips`` devices."""
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (platform {d0.platform!r}); "
                           "this smoke never runs on another backend")
    if len(devices) < chips:
        raise SmokeFailure(f"{chips} chips requested, {len(devices)} found")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def device_bytes(device, key: str = "bytes_in_use") -> int | None:
    stats = device.memory_stats() or {}
    return stats.get(key)


def count_cache_events() -> dict:
    """Persistent compilation cache hits and misses (entries written)
    from here on, as JAX reports them."""
    from jax import monitoring

    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(listen)
    return counts


def build_index(ref_len: int, table_bits: int, seed: int):
    import numpy as np
    from repro.core import SeedMapConfig, build_seedmap, random_reference

    ref = random_reference(ref_len, np.random.default_rng(seed))
    t0 = time.perf_counter()
    sm = build_seedmap(ref, SeedMapConfig(table_bits=table_bits))
    log("index", ref_len=ref_len, table_bits=table_bits,
        n_locations=int(sm.locations.shape[0]),
        build_s=time.perf_counter() - t0, peak_host_rss_gib=peak_rss_gib())
    return ref, sm


def pallas_session(sm, ref, stream_batch: int):
    """The served session; every kernel family must resolve to Pallas."""
    import jax
    from repro.core import PipelineConfig
    from repro.engine import ExecutionConfig, Mapper

    t0 = time.perf_counter()
    mapper = Mapper.from_index(
        sm, ref, PipelineConfig(),
        ExecutionConfig(stream_batch=stream_batch, tune=False))
    backends = {
        "pair_frontend": mapper.pipe_cfg.frontend_backend,
        "candidate_align": mapper.pipe_cfg.light_backend,
        "residual_dp": mapper.pipe_cfg.residual_backend,
        "location_vote": mapper.lr_cfg.vote_backend,
    }
    log("session", backends=backends, session_s=time.perf_counter() - t0,
        index_layout=type(mapper._state[0]).__name__,
        bytes_in_use=device_bytes(jax.devices()[0]),
        peak_host_rss_gib=peak_rss_gib())
    wrong = {f: b for f, b in backends.items() if b != "pallas"}
    if wrong:
        raise SmokeFailure(f"kernel families not on Pallas: {wrong}")
    return mapper


def served_path(mapper, ref, seed: int, batch: int, n_batches: int) -> dict:
    """`map_stream` with the serve CLI's device-side accuracy reduce."""
    import jax
    import jax.numpy as jnp
    from repro.core import ReadSimConfig
    from repro.data.pipeline import ReadStreamConfig, read_pairs_for_step
    from repro.launch.serve import ACC_KEYS, _make_accuracy_reduce

    cfg = mapper.pipe_cfg
    stream = ReadStreamConfig(batch=batch, read_len=cfg.read_len, seed=seed)
    sim_cfg = ReadSimConfig(read_len=cfg.read_len, sub_rate=1e-3)

    def batch_of(step):
        sim = read_pairs_for_step(ref, stream, step, sim_cfg)
        return sim.reads1, sim.reads2, (sim.true_start1, sim.true_start2)

    t0 = time.perf_counter()
    sr = mapper.map_stream(
        (batch_of(step) for step in range(1, n_batches + 1)),
        warmup_batch=batch_of(0),
        reduce_fn=_make_accuracy_reduce(cfg.max_gap),
        reduce_init={k: jnp.zeros((), jnp.int32) for k in ACC_KEYS})
    total_s = time.perf_counter() - t0
    a = {k: int(v) for k, v in sr.reduced.items()}
    n = sr.n_pairs
    acc = {
        "mapped1": a["mapped1"] / n, "mapped2": a["mapped2"] / n,
        "pair_mapped": a["pair_mapped"] / n,
        "correct_of_mapped1": a["correct1"] / max(a["mapped1"], 1),
        "correct_of_mapped2": a["correct2"] / max(a["mapped2"], 1),
        "pair_correct_of_mapped": a["pair_correct"]
        / max(a["pair_mapped"], 1),
    }
    log("served", pairs=n, batches=sr.n_batches,
        pairs_per_s_info_only=sr.pairs_per_s, stream_s=sr.seconds,
        warmup_s_compile_and_first_batch=total_s - sr.seconds,
        peak_bytes_in_use=device_bytes(jax.devices()[0],
                                       "peak_bytes_in_use"),
        **acc)
    if n != batch * n_batches:
        raise SmokeFailure(f"served {n} pairs, expected {batch * n_batches}")
    # Sanity bounds on simulated truth (random reference, 1e-3 subs);
    # the bit-identity check below is the exact test.
    if acc["pair_mapped"] < 0.9 or acc["pair_correct_of_mapped"] < 0.99:
        raise SmokeFailure(f"accuracy out of bounds: {acc}")
    return acc


def first_pairs(ref, seed: int, read_len: int, n: int):
    from repro.core import ReadSimConfig
    from repro.data.pipeline import ReadStreamConfig, read_pairs_for_step

    sim = read_pairs_for_step(
        ref, ReadStreamConfig(batch=n, read_len=read_len, seed=seed), 1,
        ReadSimConfig(read_len=read_len, sub_rate=1e-3))
    return sim.reads1, sim.reads2


def diff_fields(got, want) -> list[str]:
    import numpy as np
    return [f for f in got._fields
            if not np.array_equal(np.asarray(getattr(got, f)),
                                  np.asarray(getattr(want, f)))]


def oracle_check(mapper, sm, ref, seed: int, n: int) -> None:
    """Every `MapResult` field bit-identical to the staged jnp oracle."""
    from repro.core import PipelineConfig
    from repro.engine import ExecutionConfig, Mapper

    reads1, reads2 = first_pairs(ref, seed, mapper.pipe_cfg.read_len, n)
    t0 = time.perf_counter()
    got = mapper.map(reads1, reads2)
    oracle = Mapper.from_index(
        sm, ref, PipelineConfig(),
        ExecutionConfig(backend="jnp", tune=False))
    want = oracle.map(reads1, reads2)
    bad = diff_fields(got, want)
    log("oracle", pairs=n, fields=len(got._fields), differing=bad,
        seconds=time.perf_counter() - t0)
    if bad:
        raise SmokeFailure(f"Pallas session differs from the jnp oracle "
                           f"in {bad}")


def front_door(mapper, ref, seed: int, batch: int) -> None:
    """A short ragged two-lane trace: every request answered."""
    from repro.launch.serve import frontdoor_trace

    out = frontdoor_trace(mapper, ref, batch, 2, seed=seed)
    lost = {k: out[k] for k in ("rejected", "expired", "shed") if out[k]}
    log("frontdoor", requests=out["requests"], accepted=out["accepted"],
        completed=out["completed"], pairs=out["pairs"],
        long_reads=out["long_reads"], seconds=out["seconds"],
        pairs_per_s_info_only=out["pairs_per_s"], batches=out["batches"],
        latency=out["latency"])
    if lost or out["completed"] != out["requests"] or not out["long_reads"]:
        raise SmokeFailure(f"front door lost requests: {lost}, "
                           f"{out['completed']}/{out['requests']} answered, "
                           f"{out['long_reads']} long reads")


def sharded_index_plan(sm, ref, seed: int, batch: int) -> None:
    """Four chips: the sharded-index plan vs a single-device session."""
    import jax
    from repro.core import PipelineConfig
    from repro.engine import ExecutionConfig, Mapper
    from repro.launch.mesh import make_auto_mesh

    devices = jax.devices()[:4]
    mesh = make_auto_mesh((1, 4), ("data", "model"))
    t0 = time.perf_counter()
    sharded = Mapper.from_index(
        sm, ref, PipelineConfig(),
        ExecutionConfig(mesh=mesh, shard_index=True, tune=False))
    placement = {}
    for name, arr in zip(("offsets", "locations"), sharded._state[:2]):
        shards = arr.addressable_shards
        placement[name] = sorted((s.device.id, s.index[0].start)
                                 for s in shards)
        if (len({s.device for s in shards}) != 4
                or len({s.index[0].start for s in shards}) != 4
                or any(s.data.shape[0] != 1 for s in shards)):
            raise SmokeFailure(f"{name} is not one shard per chip: "
                               f"{placement[name]}")
    ref_words = sharded._state[2]
    placement["ref_words"] = sorted(d.id for d in ref_words.devices())
    if (len(ref_words.devices()) != 4
            or not ref_words.sharding.is_fully_replicated):
        raise SmokeFailure(f"packed reference not replicated on every chip: "
                           f"{placement['ref_words']}")
    log("sharded_session", placement=placement,
        session_s=time.perf_counter() - t0,
        bytes_in_use=[device_bytes(d) for d in devices])
    single = Mapper.from_index(
        sm, ref, PipelineConfig(),
        ExecutionConfig(packed_ref=True, tune=False))
    reads1, reads2 = first_pairs(ref, seed, PipelineConfig().read_len, batch)
    t0 = time.perf_counter()
    got = sharded.map(reads1, reads2)
    want = single.map(reads1, reads2)
    bad = diff_fields(got, want)
    log("sharded_vs_single", pairs=batch, differing=bad,
        seconds=time.perf_counter() - t0,
        peak_bytes_in_use=[device_bytes(d, "peak_bytes_in_use")
                           for d in devices])
    if bad:
        raise SmokeFailure(f"sharded-index plan differs from the "
                           f"single-device session in {bad}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    device = check_device(jax.devices(), args.chips)
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = count_cache_events()
    log("device", cache_dir=cache_dir, **device)
    ref, sm = build_index(CHR1_LEN, TABLE_BITS, args.seed)
    if args.chips == 4:
        sharded_index_plan(sm, ref, args.seed, STREAM_BATCH)
    else:
        mapper = pallas_session(sm, ref, STREAM_BATCH)
        served_path(mapper, ref, args.seed, STREAM_BATCH, STREAM_BATCHES)
        oracle_check(mapper, sm, ref, args.seed, ORACLE_PAIRS)
        front_door(mapper, ref, args.seed, STREAM_BATCH)
    log("compile_cache", dir=cache_dir, **cache_events)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
