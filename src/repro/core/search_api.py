"""Unified typed search API for every IRLI serving surface.

One request/response contract — :class:`SearchParams` in,
:class:`SearchResult` out — shared by the five serving surfaces:

  * ``IRLIIndex.search``            (frozen index)
  * ``MutableIRLIIndex.search``     (streaming index)
  * ``distributed.local_search`` / ``make_distributed_search`` /
    ``shard_search_local`` / ``make_production_search``  (sharded)
  * ``IRLIServer``                  (micro-batched serving, per-REQUEST params)

plus a :class:`Searcher` protocol so backends are interchangeable (the shape
LIRA and the multifaceted-index line of work expose), and a
:class:`PipelineCache` so the jitted query pipeline for a given
``(params, corpus size, batch bucket)`` is compiled exactly once and shared
across surfaces — per-request tunability must not mean per-request
recompilation.

The old per-surface kwarg signatures (``m=, tau=, k=, metric=, mode=,
topC=``) survive as thin shims that build a ``SearchParams`` and emit
``DeprecationWarning`` (escalated to an error for ``repro.*`` internal
callers by pytest.ini, so the library itself can never regress onto them).
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Protocol, runtime_checkable

import jax

from repro.core import query as Q

_METRICS = ("angular", "l2")
_MODES = ("auto", "dense", "compact", "mega")
_STORE_DTYPES = ("fp32", "int8", "bf16")   # mirrors store.quantized


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Everything a caller may tune about one search request.

    Frozen + hashable: usable as a jit static argument, a dict key in the
    :class:`PipelineCache`, and the grouping key of the server micro-batcher
    (requests with equal params batch together). ``mode="auto"`` is resolved
    against the actual corpus/batch size by :meth:`resolve` before any
    pipeline is built, so two requests that resolve identically share one
    compilation.
    """
    m: int = 5                 # probe width: top-m buckets per rep
    tau: int = 1               # frequency threshold (FrequentOnes)
    k: int = 10                # final top-k
    topC: int = 1024           # compact-mode candidate budget per query
    metric: str = "angular"    # "angular" | "l2"
    mode: str = "auto"         # "auto" | "dense" | "compact" | "mega"
    store_dtype: str = "fp32"  # vector tier: "fp32" | "int8" | "bf16"
    refine_k: int = 0          # exact-refine depth k' (0 = auto: max(4k,32))
    adaptive_m: bool = False   # LIRA-style per-query probe count m(q):
    #                            probes past ``probe_mass`` cumulative scorer
    #                            mass are masked out of the gather, so easy
    #                            queries touch fewer buckets (docs/online.md)
    probe_mass: float = 1.0    # target cumulative top-m probability mass per
    #                            rep; 1.0 keeps every probe (== adaptive off)
    hot_replicas: bool = False  # gather hot-bucket replica segments built by
    #                            the online refit loop (no-op when the
    #                            serving snapshot carries none)
    kernels: tuple | None = None  # Pallas kernels the stages run (a subset
    #                            of query.KERNELS); None = resolve picks
    #                            them from the backend and geometry

    def __post_init__(self):
        for name in ("m", "tau", "k", "topC"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"SearchParams.{name} must be an int >= 1, got {v!r}")
        if self.metric not in _METRICS:
            raise ValueError(f"SearchParams.metric must be one of {_METRICS},"
                             f" got {self.metric!r}")
        if self.mode not in _MODES:
            raise ValueError(f"SearchParams.mode must be one of {_MODES}, "
                             f"got {self.mode!r}")
        if self.store_dtype not in _STORE_DTYPES:
            raise ValueError(f"SearchParams.store_dtype must be one of "
                             f"{_STORE_DTYPES}, got {self.store_dtype!r}")
        rk = self.refine_k
        if not isinstance(rk, int) or isinstance(rk, bool) or rk < 0:
            raise ValueError(
                f"SearchParams.refine_k must be an int >= 0, got {rk!r}")
        for name in ("adaptive_m", "hot_replicas"):
            if not isinstance(getattr(self, name), bool):
                raise ValueError(f"SearchParams.{name} must be a bool, got "
                                 f"{getattr(self, name)!r}")
        pm = self.probe_mass
        if not isinstance(pm, (int, float)) or isinstance(pm, bool) \
                or not 0.0 < float(pm) <= 1.0:
            raise ValueError(
                f"SearchParams.probe_mass must be in (0, 1], got {pm!r}")
        if self.mode == "dense" and self.store_dtype != "fp32":
            raise ValueError(
                "mode='dense' cannot serve a quantized store "
                f"(store_dtype={self.store_dtype!r}): the dense rerank "
                "would decode the whole [L, D] corpus back to fp32")
        if self.kernels is not None:
            Q.check_kernels(self.kernels, self.mode)

    def replace(self, **kw) -> "SearchParams":
        return dataclasses.replace(self, **kw)

    def resolve(self, n_labels: int, q_batch: int = 512, *,
                width: int | None = None) -> "SearchParams":
        """Materialize ``mode="auto"`` and ``kernels=None`` — the ONE place
        a request's execution path is chosen; dispatch only reads it back.

        mode follows ``query.select_mode``: dense while the
        [q_batch, n_labels] tables fit the budget — accounting CODE bytes,
        so a quantized ``store_dtype`` never resolves dense — otherwise
        compact. kernels follows ``query.select_kernels`` from the backend
        and ``width``, the candidate slots per query (see
        :func:`candidate_width`; None = unknown, which keeps the
        frequency stage on jnp). Explicit kernels are checked against the
        same facts and never rewritten. Resolved params are the cache
        key."""
        mode = self.mode
        if mode == "auto":
            mode = Q.select_mode(n_labels, q_batch,
                                 store_dtype=self.store_dtype)
        backend = jax.default_backend()
        kernels = self.kernels
        if kernels is None:
            kernels = Q.select_kernels(mode, self.store_dtype, width,
                                       backend)
        elif kernels:
            if backend != "tpu":
                raise ValueError(f"Pallas kernels {kernels} need a TPU "
                                 f"backend, not {backend!r}")
            if "freq_topc" in kernels and "freq_topc" not in \
                    Q.select_kernels(mode, self.store_dtype, width, backend):
                raise ValueError(f"freq_topc cannot serve a candidate "
                                 f"width of {width} slots per query")
        return self.replace(mode=mode, kernels=kernels)

    def pipeline(self) -> Q.QueryPipeline:
        """The QueryPipeline realizing these params. Resolve first."""
        if self.mode == "auto" or self.kernels is None:
            raise ValueError("resolve() SearchParams before building a "
                             "pipeline — mode='auto' is not executable")
        return Q.QueryPipeline(m=self.m, tau=self.tau, k=self.k,
                               mode=self.mode, topC=self.topC,
                               metric=self.metric,
                               store_dtype=self.store_dtype,
                               refine_k=self.refine_k,
                               adaptive_m=self.adaptive_m,
                               probe_mass=float(self.probe_mass),
                               kernels=self.kernels)


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """The response of every serving surface.

    ids/scores are [Q, k] (a single server request gets its [k] row),
    ``ids`` padded with -1 where fewer than k candidates survived,
    ``n_candidates`` the per-query survivor count (capped at ``topC`` in
    compact mode, summed over shards on the distributed surfaces),
    ``epoch`` the snapshot epoch served (0 for frozen indexes), and
    ``mode`` the backend that actually ran ("dense" | "compact" | "mega")
    after auto-resolution, and ``kernels`` the Pallas kernels its stages
    ran (() = every stage in jnp).
    """
    ids: Any
    scores: Any
    n_candidates: Any
    epoch: int = 0
    mode: str = "compact"
    kernels: tuple = ()


@runtime_checkable
class Searcher(Protocol):
    """Anything that serves a typed search request. Backends (frozen,
    streaming, sharded, remote) are interchangeable behind this."""

    def search(self, queries, params: SearchParams) -> SearchResult:
        ...


@dataclasses.dataclass
class _FnSearcher:
    fn: Any

    def search(self, queries, params: SearchParams) -> SearchResult:
        return self.fn(queries, params)


def as_searcher(fn) -> Searcher:
    """Wrap ``fn(queries, params) -> SearchResult`` into a Searcher (e.g. to
    bind a frozen index to its corpus: ``as_searcher(lambda q, p:
    idx.search(q, base, p))``)."""
    return _FnSearcher(fn)


# ------------------------------------------------------------------- cache --
class PipelineCache:
    """Compiled-pipeline cache keyed on ``(resolved SearchParams, n_labels,
    q_bucket)``.

    Each entry is one jitted end-to-end search function; looking the same
    key up N times reuses the SAME function object, so XLA compiles it once
    per input structure. ``hits``/``misses`` count key lookups;
    ``compiles`` counts actual traces (a trace-time side effect — it also
    catches retraces from a changed delta/tombstone structure under one
    key). Thread-safe: the server batcher and client threads share one
    instance.

    Observability (docs/observability.md): lookups mirror into
    ``cache_{hits,misses,compiles}_total`` counters of ``registry`` (the
    process-wide ``obs.DEFAULT_REGISTRY`` when None), and :meth:`search`
    times the FIRST invocation of every fresh entry — trace + XLA compile +
    first run, fenced — into the ``cache_compile_seconds`` histogram. A
    compile whose pipeline runs the frequency kernel also counts into
    ``freq_topc_compiles_total{keys="id"|"lane"}``, by the key form the
    kernel's top-C network sorts at those shapes. The legacy ``stats()``
    dict keeps its exact four-key shape.
    """

    def __init__(self, registry=None):
        self._fns: dict = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        self._registry = registry

    @property
    def registry(self):
        """The metrics registry this cache records into (resolved lazily so
        a bare ``PipelineCache()`` built before obs configuration still
        lands in the process default)."""
        from repro import obs
        return obs.get_registry(self._registry)

    def __len__(self) -> int:
        return len(self._fns)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "compiles": self.compiles, "entries": len(self._fns)}

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()

    def _lookup(self, params: SearchParams, n_labels: int, q_bucket: int):
        """:meth:`get` plus a freshness bit -> (fn, fresh). ``fresh`` means
        the entry was just built, i.e. the fn's first call will trace and
        compile — :meth:`search` uses it to time compile latency."""
        if params.mode == "auto" or params.kernels is None:
            raise ValueError("PipelineCache keys need resolved params — "
                             "call params.resolve(n_labels, q_batch) first")
        key = (params, int(n_labels), int(q_bucket))
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                self.registry.counter("cache_hits_total").inc()
                return fn, False
            self.misses += 1
            self.registry.counter("cache_misses_total").inc()
            pipe = params.pipeline()

            def run(scorer_params, members, base, queries, delta_members,
                    tombstone):
                self.compiles += 1      # trace-time only: counts compilations
                self.registry.counter("cache_compiles_total").inc()
                if "freq_topc" in pipe.kernels:   # the keys its top-C sorts
                    from repro.kernels.freq_topc.freq_topc import key_form
                    keys = key_form(**pipe.freq_bounds(members, base,
                                                       delta_members))
                    self.registry.counter("freq_topc_compiles_total",
                                          {"keys": keys}).inc()
                return pipe.search(scorer_params, members, base, queries,
                                   delta_members, tombstone)

            fn = jax.jit(run)
            self._fns[key] = fn
            return fn, True

    def get(self, params: SearchParams, n_labels: int, q_bucket: int):
        """The jitted search fn for one resolved-params/corpus/batch key:
        ``fn(scorer_params, members, base, queries, delta_members,
        tombstone) -> (ids, scores, n_candidates)``."""
        return self._lookup(params, n_labels, q_bucket)[0]

    def search(self, params: SearchParams, scorer_params, members, base,
               queries, delta_members=None, tombstone=None, *,
               epoch: int = 0, staged: bool = False) -> SearchResult:
        """Resolve params against this corpus/batch, fetch-or-compile the
        pipeline, run it, and wrap the typed result. ``base`` is the raw
        [L, d] corpus or a QuantizedStore over it (checked against
        ``params.store_dtype``).

        ``staged=True`` routes through the per-stage debug mode
        (``QueryPipeline.search_staged``): same primitive sequence, each
        stage separately jitted + fenced and timed into this cache's
        registry under ``serve_stage_seconds{stage=...}``. Results are
        bit-identical to the fused path."""
        check_store("PipelineCache.search", params, base)
        resolved = params.resolve(
            int(base.shape[0]), int(queries.shape[0]),
            width=candidate_width(params.m, members, delta_members))
        if staged:
            pipe = resolved.pipeline()
            ids, scores, n_cand = pipe.search_staged(
                scorer_params, members, base, queries, delta_members,
                tombstone, registry=self.registry)
            return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                                epoch=epoch, mode=resolved.mode,
                                kernels=resolved.kernels)
        fn, fresh = self._lookup(resolved, base.shape[0], queries.shape[0])
        if fresh:
            from repro import obs
            with obs.trace(self.registry, "cache_compile_seconds") as sp:
                ids, scores, n_cand = sp.fence(
                    fn(scorer_params, members, base, queries, delta_members,
                       tombstone))
        else:
            ids, scores, n_cand = fn(scorer_params, members, base, queries,
                                     delta_members, tombstone)
        return SearchResult(ids=ids, scores=scores, n_candidates=n_cand,
                            epoch=epoch, mode=resolved.mode,
                            kernels=resolved.kernels)


def candidate_width(m: int, members, delta_members=None) -> int:
    """Candidate slots per query of one search: R·m·(ML [+ DL]) from the
    member matrix [R, B, ML] and the optional delta segments [R, B, DL] —
    the width ``SearchParams.resolve`` sizes the frequency stage by."""
    R, _, ML = members.shape
    DL = 0 if delta_members is None else delta_members.shape[-1]
    return int(R * m * (ML + DL))


#: Process-wide default cache: surfaces that aren't handed a private cache
#: (e.g. a bare ``idx.search``) all share this one.
DEFAULT_CACHE = PipelineCache()


def check_store(surface: str, params: SearchParams, base) -> None:
    """Fail fast when the ``store_dtype`` knob and the actual base payload
    disagree — a mismatch would otherwise surface as a shape/dtype error
    deep inside the jitted pipeline (or, worse, silently rerank on raw
    int8 codes as if they were coordinates)."""
    from repro.store.quantized import (QuantizedStore,    # lazy: no cycle
                                       check_scales)
    if isinstance(base, QuantizedStore):
        check_scales(base)
        if params.store_dtype != base.dtype:
            raise ValueError(
                f"{surface}: params.store_dtype={params.store_dtype!r} but "
                f"the base store holds {base.dtype!r} codes — build the "
                f"params with store_dtype={base.dtype!r}")
    elif params.store_dtype != "fp32":
        raise ValueError(
            f"{surface}: params.store_dtype={params.store_dtype!r} needs a "
            "QuantizedStore base — encode the corpus once with "
            "repro.store.encode(base, dtype=...) (docs/store.md)")


def check_params(surface: str, params) -> SearchParams:
    """Reject a non-SearchParams value in the params slot with a clear
    migration error. Pre-redesign call sites passed the knobs positionally
    (``idx.search(q, base, 5, 1, 10)``) — without this check such a call
    would bind an int to ``params`` and die deep inside the cache with an
    opaque AttributeError."""
    if not isinstance(params, SearchParams):
        raise TypeError(
            f"{surface} takes a SearchParams in its params slot, got "
            f"{type(params).__name__} — positional m/tau/k knobs are no "
            "longer accepted; build a SearchParams (docs/search_api.md)")
    return params


# ------------------------------------------------------------- deprecation --
_LEGACY_DEFAULTS = {"m": 5, "tau": 1, "k": 10, "topC": 1024,
                    "metric": "angular", "mode": "auto"}


def params_from_legacy_kwargs(surface: str, *, stacklevel: int = 3,
                              **kw) -> SearchParams:
    """Build SearchParams from an old-style kwarg call and warn.

    ``kw`` values of None mean "not passed" and take the shared defaults
    (identical to the old per-surface defaults, so the shim is bit-identical
    to the typed path). stacklevel=3 attributes the warning to the shim's
    CALLER, which is what pytest.ini's repro-scoped error filter matches —
    internal callers fail, external users just see the warning.
    """
    filled = {name: (default if kw.get(name) is None else kw[name])
              for name, default in _LEGACY_DEFAULTS.items()}
    warnings.warn(
        f"{surface} with bare m=/tau=/k=/metric=/mode=/topC= kwargs is "
        f"deprecated; pass SearchParams(m={filled['m']}, tau={filled['tau']},"
        f" k={filled['k']}, ...) instead (see docs/search_api.md)",
        DeprecationWarning, stacklevel=stacklevel)
    return SearchParams(**filled)


# ------------------------------------------------------- static contracts --
# Per-request tunability must not mean per-request recompilation: the cache
# compiles exactly once per (resolved params, corpus, batch bucket) key —
# audited over a sweep that repeats every key (repro.launch.audit; the same
# contract id is asserted by tests/test_analysis.py).
from repro.analysis import contracts as _C


def _cache_sweep_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.pipeline_cache_sweep()


_C.register(_C.Contract(
    id="search.cache_compiles_once",
    site="repro.core.search_api.PipelineCache",
    description="a SearchParams sweep with 4 distinct resolved keys, each "
                "hit twice, traces exactly 4 pipelines — extra traces mean "
                "cache-key drift (weak types, unstable hashing)",
    fixture=_cache_sweep_fixture,
    checks=[_C.max_trace_count(4)],
))
