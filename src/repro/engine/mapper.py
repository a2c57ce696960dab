"""The `Mapper` session: canonical device-resident state, built once.

``Mapper.build`` (reference -> index -> session) and ``Mapper.from_index``
(existing CSR `SeedMap` -> session) do, exactly once, everything no
step should redo per call:

  * resolve kernel backends for every family (env override, auto rule);
  * resolve the ``packed_ref`` tri-state and 2-bit pack the reference;
  * pick the SeedMap layout the step consumes — the CSR map on the staged
    jnp oracle path; on the kernel backends, from sizes alone
    (`index_layout`), the bucket-major padded rows (row width = the
    pipeline's per-seed location cap) where they fit in half the
    device's memory, else the CSR tables, either cut into 128-lane lines
    at placement; the bucket-range `ShardedSeedMap` on the sharded-index
    mesh plan;
  * place everything on devices (replicated or sharded per the
    `ExecutionConfig`), lay out the kernel aligners' reference lines
    (`LinedRef`) from the placed reference, and jit the one step the
    session dispatches to.

``mapper.map`` is the synchronous one-batch call; ``mapper.map_stream``
is the async double-buffered host loop (`engine.stream`) — one fused
jitted dispatch per batch carrying the device-side stage totals and an
optional caller reduction.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.encoding import pack_2bit, ref_bases
from repro.core.long_read import (
    LongReadResult,
    long_stage_stat_counts,
)
from repro.core.pipeline import (
    MapResult,
    PipelineConfig,
    stage_stat_counts,
)
from repro.core.seedmap import (
    LinedCSRSeedMap,
    LinedSeedMap,
    PaddedSeedMap,
    SeedMap,
    SeedMapConfig,
    build_seedmap,
    padded_to_csr,
    to_lined,
    to_lined_csr,
    to_padded,
)
from repro.engine.config import (
    ExecutionConfig,
    resolved_long_read,
    resolved_pipeline,
)
from repro.engine import plan
from repro.engine.spans import note_trace, set_counter, span
from repro.engine.stats import (
    LONG_STAT_KEYS,
    STAT_KEYS,
    fetch_stage_totals,
    init_stage_totals,
)
from repro.engine.stream import (
    StreamResult,
    pad_tail,
    run_stream,
    split_batch,
)
from repro.kernels._util import lined_ref

_DONATE_MSG = ".*donated.*"   # XLA's unusable-donation note, expected on CPU

#: `Mapper._fused_cache` bound: distinct (lane, reduce_fn) fused steps
#: kept per session.  Callers that pass a fresh closure per stream (the
#: bug `_make_accuracy_reduce`-style cached factories exist to avoid)
#: recompile anyway; the bound keeps them from also growing the cache
#: without limit.
_FUSED_CACHE_MAX = 8


#: ``session.index_layout`` counter value of each placed index type
_LAYOUT_NAMES = {SeedMap: "csr", PaddedSeedMap: "padded",
                 LinedCSRSeedMap: "csr_lines", LinedSeedMap: "padded_lines"}


def index_layout(table_size: int, cap: int, bytes_limit: int | None) -> str:
    """The kernel front end's index layout, from sizes alone.

    ``"padded"`` (bucket-major rows, T*cap int32) when that table fits in
    half of ``bytes_limit``, the least device memory of the session's
    devices (no limit known, as on a CPU: it fits); else ``"csr"``,
    whose locations grow with the genome and not with T*cap.
    """
    padded = table_size * cap * 4
    if bytes_limit is None or 2 * padded <= bytes_limit:
        return "padded"
    return "csr"


def _bytes_limit(mesh) -> int | None:
    """Least ``bytes_limit`` of the devices a session places on."""
    devices = (mesh.devices.flat if mesh is not None
               else jax.devices()[:1])
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return None if None in limits else min(limits)


def _place_state(index, ref_arr, cfg: PipelineConfig, mesh) -> tuple:
    """The replicated plan's device state ``(index, ref)``, placed once.

    A kernel front end reads the index in a line layout (a host reshape:
    `to_lined` of padded rows, `to_lined_csr` of the CSR tables at the
    pipeline's per-seed cap), so a genome-scale table goes to the device
    once, dense; host arrays are placed here, not per dispatch.  The
    placed layout and its device bytes are the ``spans`` counters
    ``session.index_layout`` and ``session.index_bytes``; the serve
    driver's JSON reports carry them under ``spans``, so a report says
    which layout its session placed and how large.
    A kernel aligner DMAs its windows from the reference's int32 line
    layout, which is built here from the placed reference and held as a
    `LinedRef` (span ``session.ref_layout``), so no step rebuilds it.
    """
    if cfg.frontend_backend != "jnp":
        if isinstance(index, PaddedSeedMap):
            index = to_lined(index)
        elif isinstance(index, SeedMap):
            index = to_lined_csr(index, cfg.max_locs_per_seed)
    where = NamedSharding(mesh, P()) if mesh is not None else None
    with span("session.place"):
        index, ref = jax.device_put((index, ref_arr), where)
    set_counter("session.index_layout", _LAYOUT_NAMES[type(index)])
    set_counter("session.index_bytes",
                sum(int(x.nbytes) for x in jax.tree.leaves(index)))
    if cfg.light_backend != "jnp" or cfg.residual_backend != "jnp":
        with span("session.ref_layout"):
            ref = _session_ref(ref, cfg)
    return index, ref


def _session_ref(ref, cfg: PipelineConfig):
    """The placed reference as a `LinedRef` padded for both aligners'
    windows (light: R + 2E; DP: R + 2 dp_pad): one device op."""
    widths = (cfg.read_len + 2 * cfg.max_gap, cfg.read_len + 2 * cfg.dp_pad)
    return lined_ref(ref, cfg.packed_ref, widths)


class Mapper:
    """A reusable paired-end mapping session (index + execution plan).

    Use :meth:`build` / :meth:`from_index`; the constructor wires an
    already-resolved session together.
    """

    def __init__(self, *, state: tuple, state_shardings: tuple | None,
                 raw_step, pipe_cfg: PipelineConfig,
                 exec_cfg: ExecutionConfig, sm_config: SeedMapConfig,
                 index, lr_cfg=None, raw_long_step=None):
        self._state = state          # device arrays prepended to each call
        self._state_shardings = state_shardings
        self._raw_step = raw_step    # traceable; fused into the stream step
        self.pipe_cfg = pipe_cfg     # fully resolved (concrete backends)
        self.exec_cfg = exec_cfg
        self.sm_config = sm_config
        self.index = index           # the session's resolved index object
        self._step = plan.jit_step(
            raw_step, mesh=exec_cfg.mesh,
            state_shardings=state_shardings,
            batch_axes=exec_cfg.batch_axes)
        # The long-read lane shares the session state; absent (None) on
        # sharded-index plans.
        self.lr_cfg = lr_cfg         # fully resolved LongReadConfig | None
        self._raw_long_step = raw_long_step
        self._long_step = None
        if raw_long_step is not None:
            self._long_step = plan.jit_step(
                raw_long_step, mesh=exec_cfg.mesh,
                state_shardings=state_shardings,
                batch_axes=exec_cfg.batch_axes, n_batch_args=1)
        # LRU of fused stream steps, keyed (lane, reduce_fn), bounded at
        # `_FUSED_CACHE_MAX` — see `_fused_step`.
        self._fused_cache: collections.OrderedDict = collections.OrderedDict()
        # Tune-cache snapshot the session resolved with (`from_index`
        # stamps it); persisted by `save` so a loaded worker can re-save
        # or inspect the winners its configs were resolved against.
        self._tune_entries: dict = {}

    # ------------------------------------------------------------ build --
    @classmethod
    def build(cls, ref, seedmap_cfg: SeedMapConfig | None = None,
              pipe_cfg: PipelineConfig | None = None,
              exec_cfg: ExecutionConfig | None = None) -> "Mapper":
        """Offline stage + session build: index ``ref`` and resolve."""
        seedmap_cfg = seedmap_cfg or SeedMapConfig()
        sm = build_seedmap(np.asarray(ref, dtype=np.uint8), seedmap_cfg)
        return cls.from_index(sm, ref, pipe_cfg, exec_cfg)

    @classmethod
    def from_index(cls, sm: SeedMap | PaddedSeedMap, ref,
                   pipe_cfg: PipelineConfig | None = None,
                   exec_cfg: ExecutionConfig | None = None) -> "Mapper":
        """Build a session from an existing index + reference.

        ``sm`` is a CSR `SeedMap` or an already-relaid `PaddedSeedMap`
        (the index-store load path): a padded map's row width becomes
        the session's ``max_locs_per_seed``, and a kernel session whose
        device cannot hold padded rows (`index_layout`) takes its rows
        as CSR tables — every flavor builds a bit-identical session.  A
        host padded table is made only where the layout is padded.
        ``ref`` may be the (L,) uint8 base array or the (Lw,) uint32
        2-bit packing; whichever flavor the resolved plan needs that is
        missing is derived here, once.
        """
        pipe_cfg = pipe_cfg or PipelineConfig()
        exec_cfg = exec_cfg or ExecutionConfig()
        # Tune-cache winners (if any) are read once, here, and fill only
        # knobs the configs left unset — explicit config > tune cache >
        # hand-picked defaults (`ExecutionConfig.tune`, repro.tune).
        from repro.tune import session_cache
        tune_cache = session_cache(exec_cfg.tune)
        cfg = resolved_pipeline(pipe_cfg, exec_cfg, tune_cache=tune_cache)
        ref = jnp.asarray(ref)
        packed_in = ref.dtype == jnp.uint32
        mesh = exec_cfg.mesh

        if cfg.packed_ref:
            ref_arr = ref if packed_in else pack_2bit(ref)
        else:
            if packed_in:
                raise ValueError(
                    "packed_ref resolved False but ref is uint32 words;"
                    " pass the uint8 base array")
            ref_arr = ref
        if exec_cfg.shard_index:
            from repro.core.distributed import shard_seedmap
            if not isinstance(sm, SeedMap):
                raise TypeError("shard_index requires a CSR SeedMap")
            ssm = shard_seedmap(sm, mesh.shape[exec_cfg.model_axis])
            shardings = plan.serve_state_shardings(mesh,
                                                   exec_cfg.model_axis)
            state = tuple(jax.device_put(x, s) for x, s in
                          zip((ssm.offsets, ssm.locations, ref_arr),
                              shardings))
            raw = plan.raw_sharded_index_step(
                mesh, cfg, sm.config, exec_cfg.batch_axes,
                exec_cfg.model_axis)
            index = ssm
        else:
            if isinstance(sm, PaddedSeedMap):
                # An already-padded map's row width IS the per-seed
                # location cap, so the resolved config (and the
                # long-read lane / tune bucket keys derived from it) must
                # agree with it.
                cap = int(sm.rows.shape[1])
                if cap != cfg.max_locs_per_seed:
                    cfg = dataclasses.replace(cfg, max_locs_per_seed=cap)
            index = sm
            if cfg.frontend_backend != "jnp":
                # Kernel front end: padded rows where they fit the
                # device, else the CSR tables (`index_layout`); either
                # is cut into lines at placement.  The staged oracle
                # path takes the map as given.
                layout = index_layout(sm.config.table_size,
                                      cfg.max_locs_per_seed,
                                      _bytes_limit(mesh))
                if layout == "csr" and isinstance(sm, PaddedSeedMap):
                    index = padded_to_csr(sm)
                elif layout == "padded" and isinstance(sm, SeedMap):
                    index = to_padded(sm, cap=cfg.max_locs_per_seed)
            shardings = None
            if mesh is not None:
                repl = NamedSharding(mesh, P())
                shardings = (repl, repl)
            state = _place_state(index, ref_arr, cfg, mesh)
            raw = plan.raw_pipeline_step(cfg)
        lr_cfg = raw_long = None
        if not exec_cfg.shard_index:
            lr_cfg = resolved_long_read(cfg, exec_cfg,
                                        tune_cache=tune_cache)
            raw_long = plan.raw_long_read_step(lr_cfg)
        mapper = cls(state=state, state_shardings=shardings, raw_step=raw,
                     pipe_cfg=cfg, exec_cfg=exec_cfg, sm_config=sm.config,
                     index=index, lr_cfg=lr_cfg, raw_long_step=raw_long)
        mapper._tune_entries = dict(tune_cache or {})
        return mapper

    # ----------------------------------------------------- index store ---
    def save(self, path) -> str:
        """Persist the resolved session to an index store at ``path``.

        Writes the versioned manifest + ``.npy`` payloads
        (`engine.index_store`): resolved reference flavor, resolved
        SeedMap layout, resolved pipeline / long-read / seedmap configs
        and the session's tune-cache snapshot.  ``Mapper.load`` rebuilds
        a bit-identical session from it without calling `build_seedmap`.
        Returns the manifest path.
        """
        from repro.engine.index_store import save_store
        if self.exec_cfg.shard_index:
            raise NotImplementedError(
                "saving a shard_index session is not supported; save a "
                "replicated-plan session (CSR layout) and load the store "
                "into the sharded ExecutionConfig instead")
        return save_store(path, index=self.index,
                          ref=ref_bases(self._state[1]),
                          pipe_cfg=self.pipe_cfg, sm_config=self.sm_config,
                          lr_cfg=self.lr_cfg,
                          tune_entries=self._tune_entries)

    @classmethod
    def load(cls, path, exec_cfg: ExecutionConfig | None = None, *,
             fallback_ref=None, seedmap_cfg: SeedMapConfig | None = None,
             pipe_cfg: PipelineConfig | None = None) -> "Mapper":
        """Cold-start a session from a saved index store — no index build.

        The store's configs are already fully resolved, so the session
        comes up bit-identical to the one that saved it; `build_seedmap`
        is never called.  A corrupt / stale / version-mismatched store
        warns and degrades to a full ``Mapper.build(fallback_ref, ...)``
        when ``fallback_ref`` is given (the never-crash-a-worker
        contract); with no fallback an unreadable store raises
        `IndexStoreError` — there is nothing to build from.

        ``exec_cfg`` supplies the *execution* side only (mesh, stream
        batch, donation); its ``tune=None`` default is forced to False so
        a load-time ``REPRO_TUNE_CACHE`` env cannot re-fill knobs and
        break bit-identity (pass an explicit ``tune=`` to opt back in),
        and its ``long_read=None`` default adopts the store's resolved
        lane config.
        """
        from repro.engine.index_store import IndexStoreError, load_store
        with span("session.load"):
            with span("session.store_read"):
                payload = load_store(path)
            if payload is None:
                if fallback_ref is None:
                    raise IndexStoreError(
                        f"index store {os.fspath(path)!r} is unreadable and "
                        "no fallback_ref was provided to rebuild from")
                warnings.warn(
                    f"index store {os.fspath(path)!r} unreadable; rebuilding "
                    "the session from the reference", stacklevel=2)
                return cls.build(fallback_ref, seedmap_cfg, pipe_cfg, exec_cfg)
            exec_cfg = exec_cfg or ExecutionConfig()
            if exec_cfg.tune is None:
                exec_cfg = dataclasses.replace(exec_cfg, tune=False)
            if exec_cfg.long_read is None and payload.lr_cfg is not None \
                    and not exec_cfg.shard_index:
                exec_cfg = dataclasses.replace(exec_cfg,
                                               long_read=payload.lr_cfg)
            mapper = cls.from_index(payload.index, payload.ref,
                                    payload.pipe_cfg, exec_cfg)
            mapper._tune_entries = dict(payload.tune_entries)
            return mapper

    def swap_index(self, store, *, strict: bool = False) -> str:
        """Hot-swap the device-resident index from a saved store.

        Safe between stream dispatches: the session state is *passed* to
        the jitted steps (never closed over), so a store with the same
        array shapes/dtypes and the same resolved configs just replaces
        ``self._state`` (the aligners' reference lines are laid out
        anew) — every compiled step (and the fused-step cache) stays
        valid, and the very next dispatch serves the new index.  A
        store with different shapes or configs rebuilds the session
        in-place with a warning (compiled steps retrace on next use; do
        not rebuild mid-stream — `map_stream` captures its step once).

        Returns ``"reused"`` (state swapped under the compiled steps),
        ``"rebuilt"`` (full in-place re-resolution), or ``"kept"`` (the
        store was unreadable — warned and degraded to the index already
        being served, the never-crash-a-worker contract).
        ``store`` may be a path or an already-loaded `StorePayload`.
        """
        from repro.engine.index_store import StorePayload, load_store
        if self.exec_cfg.shard_index:
            raise NotImplementedError(
                "swap_index is not supported on shard_index sessions")
        payload = (store if isinstance(store, StorePayload)
                   else load_store(store, strict=strict))
        if payload is None:
            warnings.warn("swap_index: unreadable store; keeping the "
                          "index already being served", stacklevel=2)
            return "kept"
        same_cfg = (payload.pipe_cfg == self.pipe_cfg
                    and payload.sm_config == self.sm_config
                    and payload.lr_cfg == self.lr_cfg
                    and type(payload.index) is type(self.index))
        old_leaves = jax.tree.leaves((self.index,
                                      ref_bases(self._state[1])))
        new_leaves = jax.tree.leaves((payload.index, payload.ref))
        same_shapes = same_cfg and len(old_leaves) == len(new_leaves) \
            and all(np.asarray(o).shape == np.asarray(n).shape
                    and np.asarray(o).dtype == np.asarray(n).dtype
                    for o, n in zip(old_leaves, new_leaves))
        if same_shapes:
            self._state = _place_state(payload.index, payload.ref,
                                       self.pipe_cfg, self.exec_cfg.mesh)
            self.index = payload.index
            return "reused"
        warnings.warn(
            "swap_index: store differs in shape or config from the live "
            "session; rebuilding in place (compiled steps retrace on "
            "next use)", stacklevel=2)
        exec_cfg = self.exec_cfg
        if exec_cfg.tune is None:
            exec_cfg = dataclasses.replace(exec_cfg, tune=False)
        if payload.lr_cfg is not None:
            exec_cfg = dataclasses.replace(exec_cfg,
                                           long_read=payload.lr_cfg)
        fresh = Mapper.from_index(payload.index, payload.ref,
                                  payload.pipe_cfg, exec_cfg)
        fresh._tune_entries = dict(payload.tune_entries)
        self.__dict__.update(fresh.__dict__)
        return "rebuilt"

    # ------------------------------------------------------------- run ---
    def map(self, reads1, reads2) -> MapResult:
        """Map one fixed-shape batch of FR read pairs.

        ``reads2`` as-sequenced (reverse strand); on the replicated
        plan, results are bit-identical to ``map_pairs_impl`` jitted on
        the same index and reference flavor.
        """
        reads1 = jnp.asarray(reads1)
        reads2 = jnp.asarray(reads2)
        n = jnp.int32(reads1.shape[0])
        return self._step(*self._state, reads1, reads2, n)

    def map_long(self, reads) -> LongReadResult:
        """Map one fixed-shape batch of long reads (B, L) uint8.

        Reads are expected in reference orientation, exactly the
        `core.long_read.map_long_reads` contract; results are
        bit-identical to it under the session's resolved lane config
        (``self.lr_cfg``).
        """
        if self._long_step is None:
            raise NotImplementedError(
                "the long-read lane is not available on shard_index "
                "sessions; build a replicated-index Mapper for map_long")
        reads = jnp.asarray(reads)
        n = jnp.int32(reads.shape[0])
        return self._long_step(*self._state, reads, n)

    # ---------------------------------------------------------- stream ---
    #: per-lane stream plumbing: (raw-step attr, stat counts fn, stat
    #: keys, read arrays per batch item)
    _LANES = {
        "pairs": ("_raw_step", stage_stat_counts, STAT_KEYS, 2),
        "long": ("_raw_long_step", long_stage_stat_counts,
                 LONG_STAT_KEYS, 1),
    }

    def _fused_cached(self, key, build):
        """Fetch-or-build a fused stream step in the session's bounded
        LRU (`_FUSED_CACHE_MAX`).  Shared by `_fused_step` and the
        multi-host twin (`engine.multihost._fused_masked_step`), so both
        step families compete for the same bound."""
        if key in self._fused_cache:
            self._fused_cache.move_to_end(key)
            return self._fused_cache[key]
        step = build()
        self._fused_cache[key] = step
        while len(self._fused_cache) > _FUSED_CACHE_MAX:
            self._fused_cache.popitem(last=False)
        return step

    def _fused_step(self, reduce_fn, lane: str = "pairs"):
        """One jitted dispatch per stream batch: step + totals + reduce.

        ``fused(state, carry, *reads, n, aux)`` with ``carry =
        (stage_totals, reduce_state)`` donated — the rolling accumulators
        never round-trip the host — and the read buffers donated too
        (`ExecutionConfig.donate_reads`).

        Steps are cached per ``(lane, reduce_fn)`` in a bounded LRU:
        passing the *same* reduce callable across streams (use a cached
        factory like `launch.serve._make_accuracy_reduce`, not a fresh
        closure per call) reuses the jitted step; distinct callables
        evict the least recently used entry past `_FUSED_CACHE_MAX`.
        Each trace of the body counts once under ``fused.<lane>[.<reduce
        fn>]`` in `engine.spans`' trace counts, so a recompiling step
        shows there.
        """
        raw_attr, counts_fn, keys, n_arrays = self._LANES[lane]
        raw = getattr(self, raw_attr)
        mesh = self.exec_cfg.mesh
        trace_key = f"fused.{lane}" + (
            "" if reduce_fn is None
            else f".{getattr(reduce_fn, '__qualname__', 'reduce')}")

        def build():
            def fused(state, carry, *rest):
                note_trace(trace_key)
                *reads, n, aux = rest
                res = raw(*state, *reads, n)
                totals, red = carry
                with jax.named_scope("stage_stats"):
                    counts = counts_fn(res)
                    totals = {k: totals[k] + counts[k] for k in keys}
                if reduce_fn is not None:
                    with jax.named_scope("reduce"):
                        red = reduce_fn(red, res, aux)
                return res, (totals, red)

            donate = (1,) + (tuple(range(2, 2 + n_arrays))
                             if self.exec_cfg.donate_reads else ())
            kwargs = {"donate_argnums": donate}
            if mesh is not None:
                batch_spec = NamedSharding(mesh,
                                           P(self.exec_cfg.batch_axes))
                repl = NamedSharding(mesh, P())
                kwargs.update(
                    in_shardings=(tuple(self._state_shardings), repl)
                    + (batch_spec,) * n_arrays + (repl, batch_spec),
                    out_shardings=(batch_spec, repl),
                )
            return jax.jit(fused, **kwargs)

        return self._fused_cached((lane, reduce_fn), build)

    def _stream(self, lane, batches, on_result, reduce_fn, reduce_init,
                warmup_batch) -> StreamResult:
        """The lane-generic stream body behind `map_stream` /
        `map_long_stream`: fused dispatch, carry donation, warmup, tail
        padding and the end-of-stream stat fetch."""
        _, _, keys, n_arrays = self._LANES[lane]
        stream_batch = self.exec_cfg.stream_batch
        step = self._fused_step(reduce_fn, lane)
        # Copy reduce_init: the fused step donates its carry, and the
        # caller's arrays must survive (e.g. reuse across streams).
        carry = (init_stage_totals(keys), jax.tree.map(jnp.copy, reduce_init))

        with warnings.catch_warnings():
            # Donated read buffers have no size-matching output on CPU;
            # XLA's "donated buffers were not usable" note is expected.
            warnings.filterwarnings("ignore", message=_DONATE_MSG,
                                    category=UserWarning)
            if warmup_batch is not None:
                reads, aux = split_batch(warmup_batch, n_arrays)
                # With no pinned stream_batch, the warmup batch fixes the
                # stream shape — otherwise the first real batch would
                # retrace inside the timed region.
                if stream_batch is None:
                    stream_batch = int(np.asarray(reads[0]).shape[0])
                nb = stream_batch
                wa = jax.tree.map(lambda a: pad_tail(a, nb), aux)
                # Throwaway carry: a deep copy, because the step donates
                # its carry buffers and the real loop reuses reduce_init.
                scrap_carry = jax.tree.map(jnp.copy, carry)
                with span("stream.warmup"):
                    _, scrap = step(self._state, scrap_carry,
                                    *(pad_tail(r, nb) for r in reads),
                                    jnp.int32(nb), wa)
                    jax.block_until_ready(scrap)

            def dispatch(*args):
                nonlocal carry
                *reads, n, aux = args
                res, carry = step(self._state, carry, *reads,
                                  jnp.int32(n), aux)
                return res

            n_items, n_batches, seconds, _ = run_stream(
                dispatch, batches, stream_batch=stream_batch,
                on_result=on_result, n_arrays=n_arrays)
        totals, reduced = carry
        return StreamResult(n_pairs=n_items, n_batches=n_batches,
                            seconds=seconds,
                            totals=fetch_stage_totals(totals),
                            reduced=reduced,
                            # reads per stream item == the lane's read
                            # arrays per batch: 2 mates / 1 long read.
                            reads_per_item=n_arrays)

    def map_stream(self, batches, on_result=None, reduce_fn=None,
                   reduce_init=None, warmup_batch=None) -> StreamResult:
        """Stream ``(reads1, reads2[, aux])`` batches through the session.

        Async double-buffered host loop: next batch H2D + host-side read
        generation overlap the in-flight step; each batch is one fused
        jitted dispatch (pipeline + device-side stage totals + the
        optional ``reduce_fn``); the host syncs once, at the end.

        ``reduce_fn(state, res, aux) -> state`` is traced into the step —
        it must be pure jax and mask by ``res.n_valid`` (padded tail rows
        carry garbage).  ``aux`` is the optional third element each batch
        yields (a pytree of (B,)-leading arrays, padded alongside the
        reads).  ``warmup_batch`` — an ``(reads1, reads2[, aux])`` tuple —
        pre-compiles and pre-runs the step outside the timed region.
        ``on_result(idx, res, n_valid)`` sees each device-side result one
        batch late (pipelined).
        """
        return self._stream("pairs", batches, on_result, reduce_fn,
                            reduce_init, warmup_batch)

    def map_long_stream(self, batches, on_result=None, reduce_fn=None,
                        reduce_init=None, warmup_batch=None) -> StreamResult:
        """Stream ``(reads[, aux])`` long-read batches through the session.

        The long-read lane's `map_stream`: same fused-dispatch / carry-
        donation / ``n_valid`` tail-masking machinery, one read array per
        batch item and the lane's LONG_STAT_KEYS totals.  ``reduce_fn``
        sees `LongReadResult` batches.
        """
        if self._raw_long_step is None:
            raise NotImplementedError(
                "the long-read lane is not available on shard_index "
                "sessions; build a replicated-index Mapper for "
                "map_long_stream")
        return self._stream("long", batches, on_result, reduce_fn,
                            reduce_init, warmup_batch)
