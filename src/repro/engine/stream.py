"""The async double-buffered host loop behind ``Mapper.map_stream``.

The pre-engine serve loop was strictly serial per batch: simulate/load
reads -> dispatch the step -> immediately block on ``np.asarray`` and
seven ``float()`` stage-stat syncs -> next batch.  This loop exploits
jax's async dispatch so the stages pipeline:

  * the *next* batch is pulled from the (host-side) iterator and its H2D
    transfer started while the device still computes the current step —
    read simulation / FASTQ decode overlaps alignment;
  * each batch is ONE fused dispatch: pipeline step + device-side
    StageStats accumulation + the caller's reduction (e.g. the serve
    accuracy counters) run in a single jitted call with a donated carry,
    so the host issues no follow-up work and syncs exactly once, at the
    end;
  * per-batch read buffers are donated to XLA (they are never reused);
  * consumers observe results one batch late (``on_result`` for batch k
    fires after batch k+1 was dispatched), so even a syncing consumer
    only ever waits on work that is already complete;
  * a ragged tail batch (and its aux pytree) is padded up to the stream
    batch shape and masked via ``MapResult.n_valid`` — no recompile,
    padded rows count toward nothing.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import numpy as np

from repro.engine.spans import span
from repro.engine.stats import stage_fractions

_END = object()   # end-of-iterator sentinel for `run_stream`


@dataclasses.dataclass
class StreamResult:
    """Aggregate outcome of one `map_stream` run.

    ``totals`` are the device-accumulated Fig. 10 stage counts (python
    ints, fetched once); ``reduced`` is the final state of the caller's
    ``reduce_fn`` (device arrays, already fully computed — reading them
    costs one sync), or None.  ``seconds`` covers dispatch of the first
    batch through full drain of the last (host-side generation of the
    first batch and compile/warmup excluded).  ``n_pairs`` counts the
    stream's valid items — read pairs on `map_stream`, single long reads
    on `map_long_stream` — and ``reads_per_item`` how many reads each
    item carries (2 mates per pair, 1 per long read): the lane-aware
    bases-per-item factor behind :meth:`mbp_per_s`.
    """

    n_pairs: int
    n_batches: int
    seconds: float
    totals: dict
    reduced: object = None
    reads_per_item: int = 2
    #: fleet fault-tolerance ledger (`engine.multihost` keep-alive /
    #: chaos runs): per-host batch & keep-alive counts, watchdog states,
    #: control-word log and drain reason.  None on plain single-host
    #: streams — the keep-alive machinery is bypassed there.
    health: dict | None = None

    @property
    def pairs_per_s(self) -> float:
        return self.n_pairs / max(self.seconds, 1e-9)

    def mbp_per_s(self, read_len: int) -> float:
        bases = self.n_pairs * self.reads_per_item * read_len
        return bases / max(self.seconds, 1e-9) / 1e6

    @property
    def fractions(self) -> dict:
        return stage_fractions(self.totals)


def pad_tail(arr, batch: int):
    """Zero-pad axis 0 of a ragged tail array up to the fixed stream shape.

    Scalar (0-d) aux leaves — per-batch values like a step id — have no
    batch axis to pad and pass through unchanged.
    """
    arr = np.asarray(arr)
    if arr.ndim == 0:
        return arr
    if arr.shape[0] == batch:
        return arr
    if arr.shape[0] > batch:
        raise ValueError(
            f"stream batch of {arr.shape[0]} rows exceeds the session's "
            f"fixed stream_batch={batch}")
    pad = np.zeros((batch - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def split_batch(item, n_arrays: int = 2):
    """(arr_0, ..., arr_{n-1}[, aux]) -> ((arr_0, ...), aux_pytree).

    ``n_arrays`` is the lane's read-array count per batch item: 2 mates
    on `map_stream`, 1 read batch on `map_long_stream`.
    """
    if len(item) == n_arrays:
        return tuple(item), ()
    if len(item) != n_arrays + 1:
        raise ValueError(
            f"stream batch items must have {n_arrays} read arrays plus an "
            f"optional aux pytree; got a length-{len(item)} tuple")
    return tuple(item[:n_arrays]), item[n_arrays]


def run_stream(dispatch, batches, *, stream_batch=None,
               on_result=None, n_arrays: int = 2) -> tuple[int, int, float,
                                                           object]:
    """Drive ``dispatch(*reads, n, aux) -> result`` over batches.

    ``batches`` yields ``(*reads,)`` or ``(*reads, aux)`` host items with
    ``n_arrays`` read arrays each; the first batch fixes the stream shape
    unless ``stream_batch`` pins it.  Returns ``(n_items, n_batches,
    seconds, last_result)``; accumulation state lives inside ``dispatch``
    (the Mapper's fused carry).  Each batch opens the spans
    ``stream.pull`` (the iterator's ``next``), ``stream.pad``,
    ``stream.dispatch`` and ``stream.retire`` (``on_result``); the final
    wait is ``stream.drain`` (`engine.spans`).
    """
    n_items = 0
    n_batches = 0
    prev = None
    res = None
    t0 = None
    it = iter(batches)
    for idx in itertools.count():
        with span("stream.pull"):
            item = next(it, _END)
        if item is _END:
            break
        with span("stream.pad"):
            reads, aux = split_batch(item, n_arrays)
            # Shape only — never np.asarray here: a multi-host global
            # array is not fully addressable, and materializing a device
            # array just for its row count would force a sync anyway.
            r0 = reads[0]
            n = int(r0.shape[0]) if hasattr(r0, "shape") \
                else int(np.asarray(r0).shape[0])
            if stream_batch is None:
                stream_batch = n
            padded = tuple(pad_tail(r, stream_batch) for r in reads)
            aux = jax.tree.map(lambda a: pad_tail(a, stream_batch), aux)
        # The clock starts at the first *dispatch*: pulling the first
        # batch from the iterator (read simulation / FASTQ decode) and
        # padding it are host-side setup, not stream time.
        if t0 is None:
            t0 = time.perf_counter()
        # Async dispatch: the host returns immediately and moves on to
        # simulate/transfer the next batch while the device works.
        with span("stream.dispatch"):
            res = dispatch(*padded, n, aux)
        n_items += n
        n_batches += 1
        if prev is not None and on_result is not None:
            with span("stream.retire"):
                on_result(*prev)
        prev = (idx, res, n)
    if prev is not None and on_result is not None:
        with span("stream.retire"):
            on_result(*prev)
    if res is not None:
        with span("stream.drain"):
            jax.block_until_ready(res)
    seconds = 0.0 if t0 is None else time.perf_counter() - t0
    return n_items, n_batches, seconds, res
