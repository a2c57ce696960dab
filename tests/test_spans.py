"""The program's span-and-counter table (`repro.engine.spans`).

  * spans nest, count and time on the host clock, per name;
  * JAX's compile events land under the innermost open span, never under
    an outer one, and nowhere when no span is open; nested jaxpr traces
    are counted once in the seconds (interval union);
  * the trace counter bumps once per (re)trace of a jitted body, not per
    call;
  * ``reset()`` empties every table; a counter keeps its latest value;
  * placing a session's index sets the layout and device-byte counters;
  * a 3-batch interpret-mode `map_stream` opens one ``stream.dispatch``
    per batch, its warm-up holds the step's trace and lowering, the
    fused step traced once, and its results match ``mapper.map``;
  * the front door opens its ``door.*`` spans per batch.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs,
)
from repro.engine import ExecutionConfig, FrontDoor, FrontDoorConfig, Mapper
from repro.engine import spans

COMPILE_EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                  "backend_compile_duration")


@pytest.fixture(autouse=True)
def clean_table():
    spans.reset()
    yield
    spans.reset()


def _fresh_fn(k: float):
    """A function no earlier test has traced, so calling it compiles."""
    return lambda x: x * k + 1.0


def test_spans_nest_and_count():
    with spans.span("outer"):
        for _ in range(3):
            with spans.span("inner"):
                pass
    table = spans.snapshot()["spans"]
    assert table["outer"]["count"] == 1
    assert table["inner"]["count"] == 3
    assert table["outer"]["seconds"] >= table["inner"]["seconds"] >= 0


def test_span_closes_on_error():
    with pytest.raises(ValueError):
        with spans.span("failing"):
            raise ValueError("boom")
    assert spans.snapshot()["spans"]["failing"]["count"] == 1
    # the stack unwound: an event now lands under no span
    jax.jit(_fresh_fn(11.0))(jnp.arange(3.0)).block_until_ready()
    assert not any("/" in k for k in spans.snapshot()["spans"])


def test_compile_events_land_under_the_innermost_span():
    x = jnp.arange(5.0)
    with spans.span("outer"):
        with spans.span("inner"):
            jax.jit(_fresh_fn(3.0))(x).block_until_ready()
    table = spans.snapshot()["spans"]
    for event in COMPILE_EVENTS:
        assert table[f"inner/{event}"]["count"] >= 1
        assert table[f"inner/{event}"]["seconds"] > 0
        assert f"outer/{event}" not in table
    # compiling outside every span records nothing new
    before = set(table)
    jax.jit(_fresh_fn(5.0))(x).block_until_ready()
    assert set(spans.snapshot()["spans"]) == before


def test_nested_traces_count_once_in_seconds():
    inner = jax.jit(_fresh_fn(7.0))

    @jax.jit
    def outer(x):
        return inner(x) * 2.0

    with spans.span("step"):
        outer(jnp.arange(4.0)).block_until_ready()
    table = spans.snapshot()["spans"]
    traced = table["step/jaxpr_trace_duration"]
    assert traced["count"] >= 2          # the outer trace and the inner one
    assert traced["seconds"] <= table["step"]["seconds"]


def test_event_intervals_merge():
    t = spans._Table()
    t.add_event("e", 0.0, 10.0)
    t.add_event("e", 2.0, 3.0)           # nested: adds nothing
    t.add_event("e", 20.0, 25.0)
    t.add_event("e", 9.0, 21.0)          # bridges both
    entry = t.snapshot()["spans"]["e"]
    assert entry == {"count": 4, "seconds": 25.0}
    t.add_event("e", -5.0, -4.0)         # out of order, disjoint
    t.add_event("e", 30.0, 31.0)
    t.add_event("e", -4.5, 30.5)         # covers everything
    assert t.snapshot()["spans"]["e"] == {"count": 7, "seconds": 36.0}


def test_many_sibling_events_stay_cheap():
    """A Pallas step's trace records tens of thousands of sibling events
    before their parent ends; adding them must not scan the table."""
    import time

    t = spans._Table()
    t0 = time.perf_counter()
    for k in range(50_000):
        t.add_event("e", float(k), k + 0.5)
    t.add_event("e", -1.0, 60_000.0)     # the parent absorbs them all
    assert time.perf_counter() - t0 < 5.0
    assert t.snapshot()["spans"]["e"] == {"count": 50_001,
                                          "seconds": 60_001.0}


def test_spans_are_per_thread():
    seen = {}

    def worker():
        with spans.span("worker"):
            jax.jit(_fresh_fn(13.0))(jnp.arange(2.0)).block_until_ready()
        seen["done"] = True

    with spans.span("main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=60)
    assert not th.is_alive() and seen["done"]
    table = spans.snapshot()["spans"]
    assert "worker/backend_compile_duration" in table
    assert "main/backend_compile_duration" not in table


def test_trace_counter_bumps_per_trace_not_per_call():
    @jax.jit
    def f(x):
        spans.note_trace("f")
        return x + 1

    for _ in range(3):
        f(jnp.arange(4))
    assert spans.snapshot()["traces"] == {"f": 1}
    f(jnp.arange(5))                     # a new shape retraces
    assert spans.snapshot()["traces"] == {"f": 2}


def test_reset_empties_every_table():
    with spans.span("s"):
        jax.jit(_fresh_fn(17.0))(jnp.arange(2.0)).block_until_ready()
    spans.note_trace("k")
    spans.set_counter("c", 3)
    spans.reset()
    assert spans.snapshot() == {"spans": {}, "traces": {}, "counters": {}}


def test_counter_keeps_the_latest_value():
    spans.set_counter("session.index_bytes", 10)
    spans.set_counter("session.index_bytes", 7)
    spans.set_counter("session.index_layout", "csr_lines")
    assert spans.snapshot()["counters"] == {
        "session.index_bytes": 7, "session.index_layout": "csr_lines"}


# ------------------------------------------------------- the engine ------
B = 8


@pytest.fixture(scope="module")
def tiny_world():
    rng = np.random.default_rng(5)
    ref = random_reference(30_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 3 * B, ReadSimConfig(sub_rate=3e-3), seed=6)
    return ref, sm, sim


def test_map_stream_spans_and_bit_identity(tiny_world):
    ref, sm, sim = tiny_world
    cfg = PipelineConfig(light_backend="interpret",
                         frontend_backend="interpret",
                         residual_backend="interpret")
    mapper = Mapper.from_index(sm, ref, cfg,
                               ExecutionConfig(backend="interpret",
                                               stream_batch=B))
    batches = [(sim.reads1[i:i + B], sim.reads2[i:i + B])
               for i in range(0, 3 * B, B)]
    got = {}
    spans.reset()
    sr = mapper.map_stream(iter(batches), warmup_batch=batches[0],
                           on_result=lambda i, res, n: got.update({i: res}))
    assert sr.n_batches == 3
    snap = spans.snapshot()
    table = snap["spans"]
    assert table["stream.dispatch"]["count"] == 3
    assert table["stream.pad"]["count"] == 3
    assert table["stream.retire"]["count"] == 3
    assert table["stream.pull"]["count"] == 4      # 3 batches + the end
    assert table["stream.drain"]["count"] == 1
    assert table["stream.warmup"]["count"] == 1
    for event in ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"):
        assert table[f"stream.warmup/{event}"]["seconds"] > 0
    # the window itself compiled nothing
    assert not any(k.startswith("stream.dispatch/") for k in table)
    assert snap["traces"] == {"fused.pairs": 1}
    for i, (r1, r2) in enumerate(batches):
        want = mapper.map(r1, r2)
        for f in want._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got[i], f)),
                                          np.asarray(getattr(want, f)),
                                          err_msg=f)


def test_frontdoor_opens_door_spans(tiny_world):
    ref, sm, sim = tiny_world
    mapper = Mapper.from_index(
        sm, ref, PipelineConfig(residual_capacity_frac=1.0),
        ExecutionConfig(stream_batch=B))
    fd = FrontDoor(mapper, FrontDoorConfig())
    try:
        fd._guard.uninstall()
        fd.warmup()
        spans.reset()
        fd.serve([("pairs", (sim.reads1[i:i + B], sim.reads2[i:i + B]))
                  for i in range(0, 3 * B, B)])
    finally:
        fd.close()
    table = spans.snapshot()["spans"]
    assert table["door.dispatch"]["count"] == 3
    assert table["door.retire"]["count"] == 3
    assert table["door.form_batch"]["count"] == 3


@pytest.mark.parametrize("limit,layout", [(None, "padded_lines"),
                                          (2**20, "csr_lines")])
def test_placement_counts_index_layout_and_bytes(tiny_world, monkeypatch,
                                                 limit, layout):
    """Placing a kernel session's index sets the layout and device-byte
    counters: padded lines where the rows fit the device, else the
    offsets plus the CSR location lines."""
    ref, sm, _ = tiny_world
    monkeypatch.setattr("repro.engine.mapper._bytes_limit",
                        lambda mesh: limit)
    mapper = Mapper.from_index(sm, ref, PipelineConfig(
        frontend_backend="interpret"))
    index = mapper._state[0]
    counters = spans.snapshot()["counters"]
    assert counters["session.index_layout"] == layout
    assert counters["session.index_bytes"] == sum(
        int(x.nbytes) for x in jax.tree.leaves(index))
    if layout == "csr_lines":
        assert counters["session.index_bytes"] == (
            sm.offsets.nbytes + index.lines.nbytes)
        assert index.lines.size >= sm.locations.size
