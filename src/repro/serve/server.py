"""Batched query serving for the IRLI index: admission queue + micro-batcher,
with online mutation admission for the streaming mutable index.

The paper reports per-point latencies at batch sizes 1-10k (Figs. 5-6); real
deployments amortize the R-net forward over a micro-batch. This server:
  - speaks the typed API (core/search_api): a default ``SearchParams`` at
    construction, overridable PER REQUEST (``submit(q, params)``); futures
    resolve to a per-request ``SearchResult``
  - collects requests up to ``max_batch`` or ``max_wait_ms``, grouping by
    params: same-params requests batch together, a differing-params request
    closes the current group and starts the next (arrival order preserved).
    ``SearchParams.store_dtype`` rides along like every other knob: a server
    over a quantized-store index (or with ``base`` given as a
    QuantizedStore) serves the tiered coarse+refine rerank, and fp32 vs
    int8 requests simply land in different param groups (docs/store.md)
  - pads each group to a bucket size (ladder derived from ``max_batch``, so
    a full batch never pads past itself) — one jit specialization per
    (params, bucket), compiled once and reused via this server's
    ``PipelineCache`` (hit/miss/compile counters in ``stats["cache"]``)
  - admits ``insert``/``delete`` mutations through the SAME queue, so
    updates are serialized with queries in arrival order: a mutation acts as
    a batch barrier (the in-flight query batch is served against the old
    snapshot, then the mutation is applied and the snapshot epoch advances).
    Requires the wrapped index to be a stream.MutableIRLIIndex.
  - fails all still-pending futures on close() instead of leaving callers
    blocked forever.

The old ``IRLIServer(index, m=, tau=, k=, metric=, mode=, topC=)``
constructor kwargs are a deprecated shim; a server built with EXPLICIT
legacy kwargs keeps the old future payloads (bare top-k id rows) for
bit-compatibility. A server built with no search knobs at all
(``IRLIServer(idx, base=...)``) is typed: it serves ``SearchParams()``
defaults (numerically identical to the old defaults) and its futures
resolve to ``SearchResult`` — callers that unpacked bare id rows must read
``result.ids`` (see the README migration table).
"""
from __future__ import annotations

import functools
import inspect
import itertools
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError

import numpy as np

from repro import obs
from repro.core import query as Q
from repro.core import search_api as SA
from repro.core.search_api import PipelineCache, SearchParams, SearchResult


def _fulfill(fut: Future, value) -> None:
    """set_result that tolerates a concurrently cancelled/completed future
    (client cancel() or the close() drain can race any completion)."""
    try:
        if not fut.done():
            fut.set_result(value)
    except InvalidStateError:
        pass


def _fail(fut: Future, exc: BaseException) -> None:
    try:
        if not fut.done():
            fut.set_exception(exc)
    except InvalidStateError:
        pass


def _bucket_ladder(max_batch: int) -> tuple:
    """Pad-bucket sizes clamped to max_batch: 1, 8, 32, 128, 512, ... but
    never past the largest batch the collector can form — with max_batch=64
    a full 64-request batch pads to 64, not 128 (pad_waste would otherwise
    double)."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b = 8 if b == 1 else b * 4
    out.append(max_batch)
    return tuple(out)


class IRLIServer:
    def __init__(self, index, *, params: SearchParams | None = None,
                 max_batch: int = 512, max_wait_ms: float = 2.0,
                 base=None, cache: PipelineCache | None = None,
                 registry: "obs.MetricRegistry | None" = None,
                 staged: bool = False, probe_stats: bool = True,
                 qlog: "obs.QueryLog | None" = None,
                 auditor=None, drift=None,
                 m=None, tau=None, k=None, metric=None, mode=None, topC=None):
        legacy = (params is None
                  and any(v is not None
                          for v in (m, tau, k, metric, mode, topC)))
        if legacy:
            params = SA.params_from_legacy_kwargs(
                "IRLIServer", m=m, tau=tau, k=k, metric=metric, mode=mode,
                topC=topC)
        elif params is None:
            params = SearchParams()
        elif any(v is not None for v in (m, tau, k, metric, mode, topC)):
            raise TypeError("pass either SearchParams or legacy kwargs, "
                            "not both")
        else:
            SA.check_params("IRLIServer", params)
        self.index = index
        self.default_params = params
        # legacy-constructed servers keep the old future payload (a bare
        # [k] id row); typed servers resolve futures to SearchResult
        self._legacy_results = legacy
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        self.base = base
        self.buckets = _bucket_ladder(max_batch)
        # per-server registry by default: two servers must not mix their
        # request counters (pass one explicitly to aggregate deliberately)
        self.registry = (registry if registry is not None
                         else obs.MetricRegistry())
        self.staged = staged
        self.cache = (cache if cache is not None
                      else PipelineCache(registry=self.registry))
        # mutable (stream.MutableIRLIIndex) indexes carry their own vector
        # buffer and mutation API; frozen IRLIIndex needs ``base`` to rerank
        self._mutable = hasattr(index, "insert") and hasattr(index, "delete")
        self._searcher = self._bind_searcher()
        self._probe = self._bind_probe() if probe_stats else None
        # sampled query stream for the online refit loop (docs/online.md):
        # every served batch logs (query, result ids) pairs the
        # OnlineRefitLoop later drains as incremental training data
        self.qlog = qlog
        # quality hooks (docs/quality.md) — both are hot-path cheap: the
        # auditor's observe is a sampled ring write (the exact oracle runs
        # on ITS background cadence, proven off the hot path by the
        # query.audit_oracle_off_hot_path contract), the drift recorder one
        # matmul + bincount over the batch
        self.auditor = auditor
        self.drift = drift
        self.q: queue.Queue = queue.Queue()
        self._batch_ids = itertools.count()     # serve.* spans' batch_id
        self._stop = threading.Event()
        self.registry.gauge("serve_epoch").set(getattr(index, "epoch", 0))
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def _bind_searcher(self):
        """One callable ``(queries, params) -> SearchResult`` for whatever
        backend this server wraps: a MutableIRLIIndex or any one-arg
        ``Searcher`` takes (queries, params); a frozen IRLIIndex needs
        ``base`` threaded in. None means the mask-only fallback
        (``index.query``) for a frozen index given no corpus. A backend
        whose ``search`` accepts a ``cache`` kwarg shares this server's
        PipelineCache, so ``stats["cache"]`` reflects its compilations."""
        search = getattr(self.index, "search", None)
        if search is None:
            return None
        sig = inspect.signature(search).parameters
        ckw = {"cache": self.cache} if "cache" in sig else {}
        if self.staged:
            if "staged" not in sig:
                raise TypeError(
                    "staged=True needs a backend whose search() takes a "
                    f"staged kwarg; {type(self.index).__name__}.search "
                    "does not")
            ckw["staged"] = True
        if not self._mutable and self.base is not None:
            return lambda qs, p: search(qs, self.base, p, **ckw)
        if self._mutable or not hasattr(self.index, "query"):
            return lambda qs, p: search(qs, p, **ckw)
        return None     # frozen index, no corpus: candidate-mask fallback

    def _bind_probe(self):
        """Per-bucket probe-frequency observability (the LIRA access-stats
        prerequisite): find the scorer params + (R, B) geometry on the
        wrapped index — frozen IRLIIndex directly, MutableIRLIIndex via its
        inner ``.index`` — and a flat [R·B] VectorCounter to count into.
        Returns None (disabled) when the backend exposes neither."""
        for src in (self.index, getattr(self.index, "index", None)):
            cfg = getattr(src, "cfg", None)
            if (cfg is not None and hasattr(src, "params")
                    and hasattr(cfg, "n_reps") and hasattr(cfg, "n_buckets")):
                R, B = int(cfg.n_reps), int(cfg.n_buckets)
                return src, R, B, self.registry.vector("serve_bucket_probes",
                                                       R * B)
        return None

    def _record_probes(self, queries, n: int, m: int) -> None:
        """Count which (rep, bucket) cells this batch probed into the
        ``serve_bucket_probes`` vector. Runs the probe head only (top-m on
        scorer logits, jitted per (m, shape)); pad rows are sliced off so
        padding never inflates a bucket's load."""
        src, R, B, vec = self._probe
        bidx = np.asarray(Q.probe_buckets(src.params, queries, m))[:, :n, :]
        flat = (np.arange(R)[:, None, None] * B + bidx).ravel()
        vec.inc_at(flat)

    @property
    def stats(self) -> dict:
        """Counters snapshot, including the pipeline-cache hit/miss/compile
        counts (per-request params must not mean per-request compiles).

        A VIEW over ``self.registry`` (the counters live there now — the
        old ``_stats`` dict was mutated from the batcher thread without a
        lock) kept in the legacy dict shape; the full picture is
        ``self.registry.snapshot()``."""
        reg = self.registry
        return {
            "batches": int(reg.counter("serve_batches_total").value),
            "requests": int(reg.counter("serve_requests_total").value),
            "pad_waste": int(reg.counter("serve_pad_waste_total").value),
            "param_groups": int(
                reg.counter("serve_param_groups_total").value),
            "mutations": int(reg.counter("serve_mutations_total").value),
            "epoch": int(reg.gauge("serve_epoch").value),
            "cache": self.cache.stats(),
        }

    # ------------------------------------------------------------- client --
    def _enqueue(self, op: str, payload) -> Future:
        fut: Future = Future()
        if self._stop.is_set():   # closed: fail fast instead of hanging
            fut.set_exception(RuntimeError("IRLIServer is closed"))
            return fut
        self.q.put((op, payload, fut, time.perf_counter()))
        # close() may have set _stop and drained BETWEEN the check above and
        # the put — then nobody will ever pop this item, so fail it here
        # (this path, the drain, and the batcher all use the race-safe
        # _fulfill/_fail helpers).
        if self._stop.is_set():
            _fail(fut, RuntimeError("IRLIServer is closed"))
        return fut

    def submit(self, query: np.ndarray,
               params: SearchParams | None = None) -> Future:
        """Enqueue one query; ``params`` overrides the server default for
        THIS request (it will batch with equal-params neighbors)."""
        if params is not None:
            SA.check_params("IRLIServer.submit", params)
        return self._enqueue(
            "query", (query, params if params is not None
                      else self.default_params))

    def search(self, query: np.ndarray, params: SearchParams | None = None,
               *, timeout: float | None = None):
        """Blocking submit; ``timeout`` (seconds) forwards to
        ``Future.result`` — a stuck batcher raises TimeoutError instead of
        hanging the caller forever."""
        return self.submit(query, params).result(timeout)

    def insert(self, vecs: np.ndarray) -> Future:
        """Enqueue an insert; the future resolves to the assigned ids."""
        return self._enqueue("insert", vecs)

    def delete(self, ids) -> Future:
        """Enqueue a delete; the future resolves to #newly deleted."""
        return self._enqueue("delete", ids)

    # ------------------------------------------------------------- server --
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def _apply_mutation(self, op: str, payload, fut: Future):
        try:
            if not self._mutable:
                raise TypeError(
                    f"{op} requires a MutableIRLIIndex; this server wraps a "
                    "frozen index")
            res = (self.index.insert(payload) if op == "insert"
                   else self.index.delete(payload))
            self.registry.counter("serve_mutations_total").inc()
            self.registry.gauge("serve_epoch").set(self.index.epoch)
            _fulfill(fut, res)                      # caller may have cancelled
        except Exception as e:                      # surface to the caller
            _fail(fut, e)

    def _run_batch(self, batch, params: SearchParams,
                   batch_id: int | None = None):
        """Serve one collected group. Its phases are profiler spans
        (docs/observability.md, "Program spans"), each tagged with
        ``batch_id``: ``serve.pad``, ``serve.probe_stats``,
        ``serve.search`` (dispatch), ``serve.sync`` (the waits on the
        device) and ``serve.results``. The batcher opens ``serve.batch``
        around this call, so the batch's buffers are freed inside it."""
        n = len(batch)
        nb = self._bucket(n)
        if batch_id is None:
            batch_id = next(self._batch_ids)
        reg = self.registry
        span = functools.partial(obs.trace, None, batch_id=batch_id)
        t0 = time.perf_counter()
        try:
            # stack/pad inside the try: one malformed query (wrong shape)
            # must fail ITS batch, not kill the batcher thread
            with span("serve.pad"):
                queries = np.stack([b[0] for b in batch])
                if nb > n:  # pad to bucket -> stable jit cache
                    queries = np.concatenate(
                        [queries, np.repeat(queries[-1:], nb - n, 0)])
            if self._searcher is None:
                with span("serve.search"):
                    mask, freq, _ = self.index.query(queries, m=params.m,
                                                     tau=params.tau)
                    out = list(np.asarray(mask)[:n])
                with span("serve.results"):
                    self._finish(batch, out, nb, t0)
                return
            if self._probe is not None:
                with span("serve.probe_stats"):
                    self._record_probes(queries, n, params.m)
            with span("serve.search"):
                res: SearchResult = self._searcher(queries, params)
            with span("serve.sync"):
                ids = np.asarray(res.ids)
                scores = np.asarray(res.scores)
                n_cand = np.asarray(res.n_candidates)
            with span("serve.results"):
                reg.histogram("serve_candidates",
                              bounds=obs.COUNT_BUCKETS).observe_many(
                                  n_cand[:n])
                # serve seconds for THIS batch, synchronized by the
                # np.asarray conversions above; logged per entry so the
                # shadow auditor can audit latency from the sampled stream
                dt = time.perf_counter() - t0
                if self.qlog is not None:   # pad rows sliced off first
                    self.qlog.record(queries[:n], ids[:n],
                                     epoch=int(res.epoch), latencies=dt)
                if self.auditor is not None:
                    self.auditor.observe(queries[:n], ids[:n],
                                         epoch=int(res.epoch), latency_s=dt)
                if self.drift is not None:
                    self.drift.record(queries[:n])
                if self._legacy_results:
                    out = [ids[i] for i in range(n)]
                else:
                    out = [SearchResult(ids=ids[i], scores=scores[i],
                                        n_candidates=int(n_cand[i]),
                                        epoch=res.epoch, mode=res.mode,
                                        kernels=res.kernels)
                           for i in range(n)]
                self._finish(batch, out, nb, t0)
        except Exception as e:
            for _, fut in batch:
                _fail(fut, e)

    def _finish(self, batch, out, nb: int, t0: float) -> None:
        """Count a served batch and fulfil its futures."""
        n = len(batch)
        reg = self.registry
        # the np.asarray conversions already synchronized, so this duration
        # covers dispatch + compute, not just dispatch
        reg.histogram("serve_batch_seconds").observe(time.perf_counter() - t0)
        reg.histogram("serve_batch_fill",
                      bounds=obs.COUNT_BUCKETS).observe(n)
        reg.counter("serve_batches_total").inc()
        reg.counter("serve_requests_total").inc(n)
        reg.counter("serve_pad_waste_total").inc(nb - n)
        for i, (_, fut) in enumerate(batch):
            _fulfill(fut, out[i])                   # cancelled while queued


    def _loop(self):
        pending = None   # barrier popped mid-collection: a mutation, or a
        #                  query whose params differ from the open group
        while not self._stop.is_set():
            # queue wait = enqueue -> first pop by the batcher (a parked
            # barrier item is observed at its ORIGINAL pop below, never
            # again when taken up here)
            wait_hist = self.registry.histogram("serve_queue_wait_seconds")
            if pending is not None:
                item, pending = pending, None
            else:
                try:
                    with obs.trace(None, "serve.wait"):   # an empty queue
                        item = self.q.get(timeout=0.1)
                except queue.Empty:
                    continue
                if len(item) > 3:   # tolerate raw 3-tuples (tests, clients)
                    wait_hist.observe(time.perf_counter() - item[3])
            op, payload, fut = item[:3]
            if op != "query":
                with obs.trace(None, "serve.mutation", op=op):
                    self._apply_mutation(op, payload, fut)
                continue
            group_params = payload[1]
            batch = [(payload[0], fut)]
            batch_id = next(self._batch_ids)
            with obs.trace(None, "serve.collect", batch_id=batch_id):
                deadline = time.time() + self.max_wait
                while len(batch) < self.max_batch:
                    timeout = deadline - time.time()
                    if timeout <= 0:
                        break
                    try:
                        nxt = self.q.get(timeout=timeout)
                    except queue.Empty:
                        break
                    if len(nxt) > 3:
                        wait_hist.observe(time.perf_counter() - nxt[3])
                    if nxt[0] != "query" or nxt[1][1] != group_params:
                        pending = nxt    # barrier: serve this group first
                        break
                    batch.append((nxt[1][0], nxt[2]))
            self.registry.counter("serve_param_groups_total").inc()
            with obs.trace(None, "serve.batch", batch_id=batch_id,
                           fill=len(batch), bucket=self._bucket(len(batch))):
                self._run_batch(batch, group_params, batch_id)
        # loop exited with an item parked: fail it directly — re-queueing
        # would race with close()'s drain (which may already have finished)
        if pending is not None:
            _fail(pending[2],
                  RuntimeError("IRLIServer closed before this request "
                               "was served"))

    def close(self):
        """Stop the batcher and FAIL every still-queued request — callers
        blocked on a future get an immediate error instead of hanging."""
        self._stop.set()
        # the batcher may be mid-jit-compile; draining while it still runs
        # would race completions, so wait until it has actually exited
        # (daemon thread — a stuck compile still finishes or dies with us)
        while self.thread.is_alive():
            self.thread.join(timeout=5)
        while True:
            try:
                fut = self.q.get_nowait()[2]
            except queue.Empty:
                break
            if fut is not None:
                _fail(fut, RuntimeError("IRLIServer closed before this "
                                        "request was served"))
