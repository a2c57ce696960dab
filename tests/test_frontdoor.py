"""Front-door serving-layer tests (ISSUE 7 acceptance points).

Covers the continuous-batching serve layer over one `Mapper` session:
  * a bursty ragged-arrival trace (two lanes interleaved) produces
    per-request results bit-identical to direct `mapper.map` /
    `map_long` calls on the same reads, with queue-latency percentiles
    and shed/reject counts in the report;
  * admission control: bounded queue depth rejects at saturation,
    deadline-expired requests drop at dispatch time;
  * SIGTERM (via `PreemptionGuard.request`) drains — every accepted
    request completes, the rest of the trace is shed with accounting;
  * the two-lane scheduler is starvation-free (a backlogged long lane
    is served after `long_every` pair batches);
  * a straggling step (watchdog out of HEALTHY) degrades the coalescing
    target instead of stalling the queue;
plus the serve-CLI regression: the shared ``--sub-rate`` flag must not
clobber `serve_long`'s PacBio-like 0.01 default.
"""
import json

import numpy as np
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs,
)
from repro.core.simulate import simulate_long_reads
from repro.engine import ExecutionConfig, FrontDoor, FrontDoorConfig, Mapper
from repro.engine.frontdoor import DONE, EXPIRED, REJECTED, SHED
from repro.engine.stream import pad_tail
from repro.runtime.preemption import PreemptionGuard
from repro.runtime.watchdog import DEGRADED, EVICT

B = 16          # the sessions' fixed stream batch
LONG_LEN = 600  # long-lane read length (bp)


@pytest.fixture(scope="module")
def served_world():
    rng = np.random.default_rng(0)
    ref = random_reference(60_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    # residual_capacity_frac=1.0: no DP-buffer overflow, so per-row
    # results are independent of batch composition (the front-door
    # bit-identity contract — see engine/frontdoor.py).
    mapper = Mapper.from_index(
        sm, ref, PipelineConfig(residual_capacity_frac=1.0),
        ExecutionConfig(stream_batch=B))
    sim = simulate_pairs(ref, 4 * B, ReadSimConfig(sub_rate=3e-3), seed=1)
    lreads, _ = simulate_long_reads(ref, B, LONG_LEN, 0.01, seed=2)
    return ref, mapper, sim, lreads


def _door(mapper, **cfg):
    fd = FrontDoor(mapper, FrontDoorConfig(**cfg))
    fd._guard.uninstall()   # tests drive preemption programmatically
    return fd


def _assert_rows_equal(sliced, direct, n, skip=("n_valid",)):
    for f in sliced._fields:
        if f in skip:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(sliced, f)),
            np.asarray(getattr(direct, f))[:n], err_msg=f)


# ------------------------------------------------- the acceptance test ---
def test_frontdoor_bursty_two_lane_bit_identity(served_world):
    ref, mapper, sim, lreads = served_world
    fd = _door(mapper, long_every=2)
    fd.warmup(long_reads=lreads[:1])

    def arrivals():
        """Ragged sizes, both lanes interleaved."""
        off = li = 0
        for i, n in enumerate([5, 16, 1, 9, 3, 16, 7, 7]):
            yield ("pairs", (sim.reads1[off:off + n],
                             sim.reads2[off:off + n]))
            off += n
            if i % 3 == 1 and li < len(lreads):
                m = min(3, len(lreads) - li)
                yield ("long", (lreads[li:li + m],))
                li += m

    report = fd.serve(arrivals())

    # every accepted request completed, none rejected/shed on this trace
    serve_stats = report["serve"]
    assert serve_stats["accepted"] == serve_stats["completed"] == \
        len(fd.requests)
    assert serve_stats["rejected"] == serve_stats["shed"] == 0
    assert set(report["stage_totals"]) == {"pairs", "long"}
    assert report["stage_totals"]["pairs"]["n_pairs"] == 64
    assert report["stage_totals"]["long"]["n_reads"] == 9
    # queue-latency percentiles are in the output and JSON-serializable
    lat = serve_stats["latency"]
    for comp in ("queue_wait_s", "service_s", "total_s"):
        assert lat[comp]["p99"] >= lat[comp]["p50"] >= 0.0
    json.dumps(report)

    # bit-identity: each request's result slice == a direct map/map_long
    # of exactly its reads (padded to the session shape)
    for req in fd.requests:
        assert req.status == DONE
        if req.lane == "pairs":
            direct = mapper.map(pad_tail(req.reads[0], B),
                                pad_tail(req.reads[1], B))
        else:
            direct = mapper.map_long(pad_tail(req.reads[0], B))
        _assert_rows_equal(req.result, direct, req.n)
        # the slice's own n_valid rows are all real
        assert np.asarray(req.result.n_valid).all()


# ------------------------------------------------- admission control -----
def test_frontdoor_rejects_at_queue_bound(served_world):
    _, mapper, sim, _ = served_world
    fd = _door(mapper, max_queue_rows=B)
    a = fd.submit("pairs", (sim.reads1[:10], sim.reads2[:10]))
    b = fd.submit("pairs", (sim.reads1[10:16], sim.reads2[10:16]))
    over = fd.submit("pairs", (sim.reads1[16:17], sim.reads2[16:17]))
    assert over.status == REJECTED and over.result is None
    assert fd.stats.rejected == 1 and fd.stats.rejected_rows == 1
    fd.drain()
    assert a.status == DONE and b.status == DONE
    assert fd.stats.completed_rows == 16


def test_frontdoor_deadline_expiry(served_world):
    _, mapper, sim, _ = served_world
    fd = _door(mapper)
    dead = fd.submit("pairs", (sim.reads1[:4], sim.reads2[:4]),
                     deadline_s=-1.0)     # already expired
    live = fd.submit("pairs", (sim.reads1[4:8], sim.reads2[4:8]))
    fd.drain()
    assert dead.status == EXPIRED and dead.result is None
    assert live.status == DONE
    assert fd.stats.expired == 1 and fd.stats.expired_rows == 4
    assert fd.stats.completed_rows == 4


def test_frontdoor_request_validation(served_world):
    _, mapper, sim, lreads = served_world
    fd = _door(mapper)
    with pytest.raises(ValueError, match="unknown lane"):
        fd.submit("nope", (sim.reads1[:1], sim.reads2[:1]))
    with pytest.raises(ValueError, match="read arrays"):
        fd.submit("pairs", (sim.reads1[:1],))
    with pytest.raises(ValueError, match="stream_batch"):
        fd.submit("pairs", (sim.reads1[:B + 1], sim.reads2[:B + 1]))
    with pytest.raises(ValueError, match="row count"):
        fd.submit("pairs", (sim.reads1[:2], sim.reads2[:3]))


# ---------------------------------------------- preemption-drain ---------
def test_frontdoor_sigterm_drains_accepted_requests(served_world):
    _, mapper, sim, lreads = served_world
    guard = PreemptionGuard()
    guard.uninstall()
    fd = FrontDoor(mapper, FrontDoorConfig(long_every=2), guard=guard)

    def arrivals():
        off = 0
        for i, n in enumerate([6, 16, 5, 3]):
            yield ("pairs", (sim.reads1[off:off + n],
                             sim.reads2[off:off + n]))
            off += n
        # SIGTERM-equivalent lands mid-trace: the rest must be shed
        guard.request()
        yield ("pairs", (sim.reads1[off:off + 2],
                         sim.reads2[off:off + 2]))
        yield ("long", (lreads[:2],))

    report = fd.serve(arrivals())
    accepted = [r for r in fd.requests if r.status not in (SHED, REJECTED)]
    shed = [r for r in fd.requests if r.status == SHED]
    # no lost accepted requests: everything admitted completed
    assert len(accepted) == 4
    assert all(r.status == DONE for r in accepted)
    assert len(shed) == 2 and report["serve"]["shed"] == 2
    assert report["serve"]["shed_rows"] == 4
    assert report["serve"]["completed"] == 4
    assert report["drained"]
    # the ledger flushed: stage totals match the drained rows
    assert report["stage_totals"]["pairs"]["n_pairs"] == 6 + 16 + 5 + 3


# ------------------------------------------- two-lane scheduling ---------
def test_frontdoor_long_lane_is_starvation_free(served_world):
    _, mapper, sim, lreads = served_world
    fd = _door(mapper, long_every=2)

    def arrivals():
        # a small long request lands early and never fills a batch...
        yield ("long", (lreads[:2],))
        # ...while full pair batches keep the priority lane ready
        for i in range(6):
            off = (i % 4) * B
            yield ("pairs", (sim.reads1[off:off + B],
                             sim.reads2[off:off + B]))

    fd.serve(arrivals())
    long_req = next(r for r in fd.requests if r.lane == "long")
    assert long_req.status == DONE
    # the starvation guard dispatched it mid-trace, not at the drain:
    # pair batches were still being served after it went out
    pair_after = [r for r in fd.requests if r.lane == "pairs"
                  and r.t_dispatch > long_req.t_dispatch]
    assert len(pair_after) >= 1
    assert fd.stats.batches["long"] == 1


# ------------------------------------------- straggler degrade -----------
def test_frontdoor_degraded_watchdog_shrinks_batches(served_world):
    _, mapper, sim, _ = served_world
    fd = _door(mapper, degrade_factor=0.5)
    fd._watchdogs["pairs"].state = DEGRADED
    assert fd._target("pairs") == B // 2
    for i in range(4):
        fd.submit("pairs", (sim.reads1[4 * i:4 * i + 4],
                            sim.reads2[4 * i:4 * i + 4]))
    n = fd.dispatch_ready()
    fd.drain()
    # 16 queued rows went out as two half-size batches, not one full one
    assert n == 2
    assert fd.stats.batches["pairs"] == 2
    assert fd.stats.batch_rows["pairs"] == 16
    assert fd.stats.degraded_batches == 2
    assert all(r.status == DONE for r in fd.requests)


def test_frontdoor_evict_escalates_to_drain(served_world):
    _, mapper, sim, _ = served_world
    fd = _door(mapper)

    class _Evicting:
        state = DEGRADED

        def observe(self, t):
            return EVICT

    fd._watchdogs["pairs"] = _Evicting()
    fd.submit("pairs", (sim.reads1[:B], sim.reads2[:B]))
    fd.dispatch_ready()
    fd.drain()      # retires the batch -> EVICT -> guard.request()
    assert fd._guard.should_checkpoint()
    late = fd.submit("pairs", (sim.reads1[:1], sim.reads2[:1]))
    assert late.status == SHED


# ------------------------------------------------- serve CLI regression --
def test_serve_cli_sub_rate_defaults(monkeypatch):
    """--sub-rate must default per workload: 1e-3 pairs, 0.01 long."""
    import repro.launch.serve as serve_mod

    calls = {}

    def fake_long(**kw):
        calls["long"] = kw
        return {}

    def fake_pairs(**kw):
        calls["pairs"] = kw
        return {}

    monkeypatch.setattr(serve_mod, "serve_long", fake_long)
    monkeypatch.setattr(serve_mod, "serve", fake_pairs)
    # The CLI's persistent-cache placement would outlive this test.
    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: None)

    monkeypatch.setattr("sys.argv", ["serve", "--workload", "long"])
    serve_mod.main()
    assert calls["long"]["sub_rate"] == 0.01

    monkeypatch.setattr("sys.argv", ["serve"])
    serve_mod.main()
    assert calls["pairs"]["sub_rate"] == 1e-3

    monkeypatch.setattr("sys.argv", ["serve", "--workload", "long",
                                     "--sub-rate", "5e-3"])
    serve_mod.main()
    assert calls["long"]["sub_rate"] == 5e-3
