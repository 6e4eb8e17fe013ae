"""Batched query execution — many requests, ONE compiled function.

Serving traffic arrives as small requests (often a single point); running a
jit per request would retrace on every new row count and waste the
accelerator on tiny launches.  The :class:`MicroBatcher` accumulates
requests into **fixed-size padded batches**: every flush calls the serving
function with exactly ``[batch_size, d]`` rows, so there is exactly one
compiled executable for the whole serving process.

The padded-batch contract (tests/test_serving.py pins it):

* pad rows are zero rows appended after the real queries;
* the serving function is row-independent (each output row depends only on
  its query row and the index), so the outputs for the real rows are
  **bitwise invariant** to the number of pad rows;
* pad-row outputs are sliced off before futures resolve — no caller ever
  observes a pad label.

Latency is bounded by the **max-wait flush**: a batch goes out when it is
full *or* when its oldest request has waited ``max_wait_s``, whichever
comes first — p99 ≈ max_wait_s + one model call, even at low arrival
rates.  The chip benchmark's ``dti.serve`` cell (``bench/``) drives a
Poisson trace through this exact code path and reports the p95 latency
and the labels per second the contract buys.

The flush thread marks its states with ``jax.profiler.TraceAnnotation``
spans, which land on the profiler's clock beside the device's operations
and cost about two microseconds each while no profiler runs:
``batcher.idle`` (queue empty), ``batcher.fill_wait`` (a request queued,
waiting for fill or the max wait) and ``batcher.flush`` (batch taken to
futures resolved) with its children ``batcher.assemble``,
``batcher.call``, ``batcher.to_host`` and ``batcher.resolve``.  A flush
and its children carry its running id (``flush``); the flush carries
``requests``, ``rows``, ``full`` and the requests' waits from enqueue to
take on the batcher's monotonic clock (``wait_us_sum``,
``wait_us_max``).  DESIGN.md §18 lists every span.

Failure isolation follows the PR 8 serve-loop contract: an exception in
the serving function fails the futures of that flush only; the batcher
thread survives and keeps serving subsequent batches.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BatchConfig:
    """Flush policy knobs.

    ``batch_size`` is the static row count of the one compiled function —
    pick it for the accelerator, not the traffic (pad rows are nearly free
    next to a retrace).  ``max_wait_s`` bounds the queueing delay of the
    first request in a batch; it is the knob that trades p99 against batch
    fill.
    """

    batch_size: int = 64
    max_wait_s: float = 0.01

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError(
                f"BatchConfig.batch_size must be >= 1, got {self.batch_size}")
        if self.max_wait_s <= 0:
            raise ValueError(
                f"BatchConfig.max_wait_s must be > 0, got {self.max_wait_s}")


@dataclasses.dataclass
class BatcherStats:
    """Flush accounting (read after a trace for fill/padding ratios)."""

    batches: int = 0
    rows: int = 0  # real query rows served
    pad_rows: int = 0  # zero rows added to fill batches
    full_flushes: int = 0  # batch went out because it filled
    timed_flushes: int = 0  # batch went out on the max-wait deadline
    failed_batches: int = 0  # serving-fn exceptions (futures got the error)
    split_requests: int = 0  # oversized requests split across flushes
    requests: int = 0  # requests (split chunks counted apart) served

    @property
    def fill(self) -> float:
        total = self.rows + self.pad_rows
        return self.rows / total if total else 0.0


class _Pending:
    __slots__ = ("rows", "future", "t0_ns")

    def __init__(self, rows: np.ndarray, future: Future, t0_ns: int):
        self.rows = rows
        self.future = future
        self.t0_ns = t0_ns  # enqueue time, time.monotonic_ns()


class MicroBatcher:
    """Accumulate point-labelling requests into fixed-size padded batches.

    ``fn(batch: [batch_size, d] f32) -> pytree`` is the serving function;
    every leaf of its output must have leading dimension ``batch_size``
    (rows are sliced back out per request).  Typically a
    ``functools.partial(serve_fn, index)`` closure over a
    :class:`~repro.serve.oos.ServingIndex` — swap the index between
    flushes with :meth:`set_fn` (the registry refresh path; takes effect
    on the next flush, in-flight batches finish on the old version).

    Thread-safe producers: :meth:`submit` may be called from any number of
    threads; a single background thread owns flushing.  Use as a context
    manager (or call :meth:`close`) so the flush thread drains and exits.
    """

    def __init__(self, fn: Callable[[np.ndarray], Any], feature_dim: int,
                 config: BatchConfig = BatchConfig()):
        self._fn = fn
        self.d = feature_dim
        self.config = config
        self.stats = BatcherStats()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[_Pending] = []
        self._queued_rows = 0
        self._closed = False
        self._flushes = 0  # running flush id (flush thread only)
        self._thread = threading.Thread(
            target=self._loop, name="micro-batcher", daemon=True)
        self._thread.start()

    # -- producer side ------------------------------------------------------

    def submit(self, points) -> Future:
        """Enqueue one request ([m, d] or a single [d] point); resolves to
        the serving output rows for exactly those m points.

        Requests larger than ``batch_size`` are split into consecutive
        chunks inside the batcher (the one-compiled-``serve_fn`` contract
        holds — every flush is still exactly ``[batch_size, d]``) and the
        output slices are reassembled before the returned future resolves.
        Failure isolation is per flush: if any chunk's flush fails, THIS
        request's future gets that error, while requests riding in other
        flushes — including other chunks' co-passengers — are untouched.
        """
        rows = np.asarray(points, np.float32)
        if rows.ndim == 1:
            rows = rows[None, :]
        if rows.ndim != 2 or rows.shape[1] != self.d:
            raise ValueError(
                f"request shape {rows.shape} does not match feature_dim="
                f"{self.d} (expected [m, {self.d}])")
        if rows.shape[0] > self.config.batch_size:
            return self._submit_split(rows)
        return self._enqueue(rows)

    def _enqueue(self, rows: np.ndarray) -> Future:
        fut: Future = Future()
        with self._cond:
            if self._closed:
                raise RuntimeError("MicroBatcher is closed")
            self._queue.append(_Pending(rows, fut, time.monotonic_ns()))
            self._queued_rows += rows.shape[0]
            self._cond.notify_all()
        return fut

    def _submit_split(self, rows: np.ndarray) -> Future:
        """Split an oversized request into batch-size chunks, enqueue them
        in order (consecutive flushes drain them FIFO), and resolve one
        parent future with the per-leaf concatenation of the chunk slices.
        The first chunk error wins; late results after a failure are
        dropped."""
        bs = self.config.batch_size
        chunks = [rows[off:off + bs] for off in range(0, rows.shape[0], bs)]
        parent: Future = Future()
        parts: List[Any] = [None] * len(chunks)
        state = {"left": len(chunks), "failed": False}
        lock = threading.Lock()

        def on_done(i: int):
            def cb(fut: Future) -> None:
                err = fut.exception()
                with lock:
                    if state["failed"]:
                        return
                    if err is not None:
                        state["failed"] = True
                        parent.set_exception(err)
                        return
                    parts[i] = fut.result()
                    state["left"] -= 1
                    done = state["left"] == 0
                if done:
                    parent.set_result(jax.tree.map(
                        lambda *xs: np.concatenate(xs, axis=0), *parts))
            return cb

        with self._lock:
            self.stats.split_requests += 1
        futs = [self._enqueue(c) for c in chunks]
        for i, f in enumerate(futs):
            f.add_done_callback(on_done(i))
        return parent

    def label(self, points, timeout: Optional[float] = None):
        """Synchronous convenience: submit + wait."""
        return self.submit(points).result(timeout=timeout)

    def set_fn(self, fn: Callable[[np.ndarray], Any]) -> None:
        """Swap the serving function (zero-downtime refresh: queued and
        future requests use the new one from the next flush on)."""
        with self._cond:
            self._fn = fn

    def close(self, *, drain: bool = True) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join()
        if not drain:
            return

    def __enter__(self) -> "MicroBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- flush side ---------------------------------------------------------

    def _take_batch_locked(self) -> Tuple[List[_Pending], int, bool]:
        """Pop whole requests up to batch_size rows (requests are never
        split across batches — their outputs slice out contiguously)."""
        took: List[_Pending] = []
        rows = 0
        while self._queue:
            nxt = self._queue[0]
            if rows + nxt.rows.shape[0] > self.config.batch_size:
                break
            took.append(self._queue.pop(0))
            rows += nxt.rows.shape[0]
        self._queued_rows -= rows
        return took, rows, rows == self.config.batch_size

    def _loop(self) -> None:
        cfg = self.config
        while True:
            with self._cond:
                if not self._queue and not self._closed:
                    with TraceAnnotation("batcher.idle"):
                        while not self._queue and not self._closed:
                            self._cond.wait()
                if not self._queue and self._closed:
                    return
                # wait for fill or the oldest request's deadline
                with TraceAnnotation("batcher.fill_wait"):
                    deadline = self._queue[0].t0_ns * 1e-9 + cfg.max_wait_s
                    while (self._queued_rows < cfg.batch_size
                           and not self._closed):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=remaining)
                took, rows, full = self._take_batch_locked()
                taken_ns = time.monotonic_ns()
                fn = self._fn
            if not took:
                continue
            self._flush(fn, took, rows, full, taken_ns)

    def _flush(self, fn, took: List[_Pending], rows: int, full: bool,
               taken_ns: int) -> None:
        cfg = self.config
        fid = self._flushes
        self._flushes += 1
        waits = [(taken_ns - p.t0_ns) // 1000 for p in took]
        with TraceAnnotation("batcher.flush", flush=fid, requests=len(took),
                             rows=rows, full=int(full),
                             wait_us_sum=sum(waits), wait_us_max=max(waits)):
            with TraceAnnotation("batcher.assemble", flush=fid):
                batch = np.zeros((cfg.batch_size, self.d), np.float32)
                off = 0
                offsets = []
                for p in took:
                    m = p.rows.shape[0]
                    batch[off:off + m] = p.rows
                    offsets.append((off, m))
                    off += m
            try:
                with TraceAnnotation("batcher.call", flush=fid):
                    out = fn(batch)
                with TraceAnnotation("batcher.to_host", flush=fid):
                    out = jax.tree.map(np.asarray, out)  # one host sync per flush
            except Exception as e:  # isolation: this flush fails, thread lives
                self.stats.failed_batches += 1
                for p in took:
                    p.future.set_exception(e)
                return
            self.stats.batches += 1
            self.stats.requests += len(took)
            self.stats.rows += rows
            self.stats.pad_rows += cfg.batch_size - rows
            if full:
                self.stats.full_flushes += 1
            else:
                self.stats.timed_flushes += 1
            with TraceAnnotation("batcher.resolve", flush=fid):
                for p, (o, m) in zip(took, offsets):
                    p.future.set_result(jax.tree.map(lambda a: a[o:o + m], out))
