"""One Mapper engine API: sessionized index + execution plan (docs/ENGINE.md).

The paper's pipeline (§4.1, Fig. 3) is one dataflow; this package is its
one front door.  ``Mapper.build`` / ``Mapper.from_index`` construct the
canonical device-resident state exactly once — 2-bit packed reference,
SeedMap layout (padded or CSR lines, from sizes), resolved kernel
backends, mesh/sharding placement
— in the spirit of the persistent-service mappers GenPairX is benchmarked
against (BWA-MEM2's reusable index handle; GenDP's fixed dataflow
programmed once, driven many times).  ``mapper.map`` dispatches to a
single pre-jitted step that is the same code for single-device and mesh
execution; ``mapper.map_stream`` runs the async double-buffered host loop
that keeps the fused kernels fed.

``engine.frontdoor.FrontDoor`` is the continuous-batching serve layer
over the same session: ragged per-request arrivals coalesced into the
fixed-shape batches the fused stream steps want, with admission control,
a per-request latency ledger (`ServeStats`) and a starvation-free
two-lane scheduler — the piece that turns the benchmark harness into a
service front end.

``engine.index_store`` is the fleet persistence layer: ``Mapper.save`` /
``Mapper.load`` round-trip the fully resolved session (packed reference,
padded or CSR SeedMap, resolved configs, tune snapshot) through a versioned
checksummed on-disk store so workers cold-start without rebuilding the
index, ``Mapper.swap_index`` / ``FrontDoor.reload_index`` hot-swap a new
index release into a live session, and ``engine.multihost.map_stream``
drives per-host generators through one fleet-wide SPMD dispatch.

The pre-engine entry points — `core.pipeline.map_pairs` and the
`core.distributed.make_*` factories — survive as thin deprecation shims
over the same implementations (warn once, delegate).
"""
from repro.core.long_read import LongReadConfig, LongReadResult
from repro.core.pipeline import MapResult
from repro.engine.config import ExecutionConfig
from repro.engine.frontdoor import FrontDoor, FrontDoorConfig, Request
from repro.engine.index_store import (
    IndexStoreError,
    StorePayload,
    load_store,
    save_store,
)
from repro.engine.mapper import Mapper
from repro.engine.stats import ServeStats
from repro.engine.stream import StreamResult

__all__ = ["ExecutionConfig", "FrontDoor", "FrontDoorConfig",
           "IndexStoreError", "LongReadConfig", "LongReadResult",
           "MapResult", "Mapper", "Request", "ServeStats", "StorePayload",
           "StreamResult", "load_store", "save_store"]
