"""IRLIIndex — the end-to-end orchestrator (Alg. 1 + Alg. 2).

fit():   init partitions (2-universal hash) -> loop: train R scorers for
         ``epochs_per_round`` epochs -> recompute affinities -> power-of-K
         re-partition -> rebuild inverted index. Alternation continues until
         re-assignments converge (paper: "until the number of new assignments
         converges to zero") or ``rounds`` is exhausted.
query(): Alg. 2 (top-m multiprobe + frequency filter + rerank).

Works for both ANN mode (labels are the corpus vectors; Def. 2 affinity) and
XML mode (label sets per train point; Def. 1 affinity).
"""
from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as PT
from repro.core import query as Q
from repro.core import search_api as SA
from repro.core.network import ScorerConfig, scorer_init
from repro.fit.engine import FitData, FitEngine, make_fit_optimizer
from repro.fit.state import FitState


@dataclasses.dataclass
class IRLIConfig:
    d: int
    n_labels: int
    n_buckets: int = 256
    n_reps: int = 8
    d_hidden: int = 256
    K: int = 10                    # power-of-K choices
    parallel_slack: float = 2.0    # capacity slack for repartition_mode=parallel
    # (slack 1.25 -> near-perfect balance but ~0.17 recall cost on trained,
    #  concentrated affinities; 2.0 matches exact-mode recall — EXPERIMENTS)
    rounds: int = 5                # train/re-partition alternations
    epochs_per_round: int = 5
    batch_size: int = 512
    lr: float = 1e-3
    loss: str = "softmax_bce"
    repartition_mode: str = "exact"   # exact | parallel
    max_load_slack: float = 2.0       # member-matrix pad factor over L/B
    affinity_chunk: int = 4096        # label-chunk width of the streaming
    #                                   top-K affinity (fit/affinity.py)
    seed: int = 0


@dataclasses.dataclass
class FitStats:
    round_idx: list
    n_reassigned: list
    load_std: list
    train_loss: list      # per round: mean of that round's per-epoch means
    epoch_loss: list = dataclasses.field(default_factory=list)  # per round:
    #                     the [epochs_per_round] per-epoch mean losses
    dropped_slots: int = 0  # (rep, label) slots the fitted index's member
    #                     matrix could not hold (InvertedIndex.dropped)


class IRLIIndex:
    def __init__(self, cfg: IRLIConfig):
        self.cfg = cfg
        key = jax.random.PRNGKey(cfg.seed)
        self.key, k1 = jax.random.split(key)
        self.scorer_cfg = ScorerConfig(
            d_in=cfg.d, d_hidden=cfg.d_hidden, n_buckets=cfg.n_buckets,
            n_reps=cfg.n_reps, loss=cfg.loss)
        self.params = scorer_init(k1, self.scorer_cfg)
        self.opt = make_fit_optimizer(cfg)
        self.opt_state = self.opt.init(self.params)
        self.assign = PT.hash_init(cfg.n_labels, cfg.n_buckets, cfg.n_reps,
                                   cfg.seed)
        self.index: PT.InvertedIndex | None = None
        self.epoch = 0   # artifact version served; bumped by install_artifact

    # ---------------------------------------------------------------- fit --
    def fit(self, x_train, label_ids, label_mask=None, label_vecs=None,
            verbose: bool = False, mesh=None, registry=None,
            log=None) -> FitStats:
        """x_train [N,d]; label_ids [N,k] (ANN: k exact neighbors; XML: padded
        label sets); label_vecs [L,d] enables Def.2 affinity (ANN mode).

        Thin driver over :class:`repro.fit.engine.FitEngine`: each round is
        ONE compiled call (scan over ``epochs_per_round`` epochs of padded
        fixed-size batches + streaming top-K affinity + vmapped power-of-K
        re-partition), with a single host sync per round for the paper's
        "until re-assignments converge" stop. Pass a (data × rep) ``mesh``
        (launch/mesh.make_fit_mesh) to shard batches over "data" (psum'd
        grads) and the R repetitions over "rep" — docs/fit.md.

        ``registry`` (an ``obs.MetricRegistry``) receives per-round fit
        telemetry — loss/grad-norm, re-partition churn, and the paper's
        load-balance summary (bucket min/max/std and KL-vs-uniform) — and
        ``log`` (an ``obs.MetricsLogger``) gets one JSONL row per round
        (docs/observability.md). Both default to off/None.
        """
        cfg = self.cfg
        data = FitData.build(x_train, label_ids, label_mask, label_vecs,
                             n_labels=cfg.n_labels, chunk=cfg.affinity_chunk)
        engine = FitEngine(cfg, self.scorer_cfg)
        # donate COPIES: the engine's round donates its input state, and the
        # index's live buffers (params/opt_state/assign) must survive an
        # exception mid-fit on donation-honoring backends
        state = FitState.create(
            jax.tree.map(jnp.copy, self.params),
            jax.tree.map(jnp.copy, self.opt_state),
            jnp.copy(self.assign), self.key)
        if mesh is None:
            round_fn = engine.make_fit_round(data)
        else:
            round_fn = engine.make_sharded_fit_round(mesh, data, state)

        n = data.x.shape[0]
        stats = FitStats([], [], [], [], [])
        for rnd in range(cfg.rounds):
            idx, w = engine.round_batches(n, cfg.seed, rnd)
            t0 = time.perf_counter()
            state, met = round_fn(state, idx, w)
            met = jax.tree.map(np.asarray, met)   # one host sync per round
            dt = time.perf_counter() - t0
            n_re = int(met["n_reassigned"])
            loss = float(met["loss"])
            lstd = float(met["load_std"])
            stats.round_idx.append(rnd)
            stats.n_reassigned.append(n_re)
            stats.load_std.append(lstd)
            stats.train_loss.append(loss)
            stats.epoch_loss.append(
                [float(l) for l in np.asarray(met["epoch_loss"])])
            row = self._record_fit_round(rnd, met, dt, registry)
            if log is not None:
                log.log(row, step=rnd)
            if verbose:
                print(f"[irli] round {rnd}: loss={loss:.4f} "
                      f"reassigned={n_re} load_std={lstd:.2f}")
            if n_re == 0:
                break

        self.params = state.params
        self.opt_state = state.opt_state
        self.assign = state.assign
        self.key = state.rng
        self.build_index()
        # a bucket loaded past the member width loses the overflow from that
        # rep: counted, reported and warned about, never silent
        stats.dropped_slots = self.index.dropped
        if registry is not None:
            registry.gauge("fit_index_dropped_slots").set(stats.dropped_slots)
        if stats.dropped_slots:
            warnings.warn(
                f"fit: {stats.dropped_slots} of {cfg.n_reps * cfg.n_labels} "
                f"(rep, label) slots overflow the member width "
                f"{self.index.max_load} and are left out of the index",
                RuntimeWarning, stacklevel=2)
        return stats

    def _record_fit_round(self, rnd: int, met: dict, seconds: float,
                          registry) -> dict:
        """Flatten one round's engine metrics into a JSONL-able row and,
        when ``registry`` is given, mirror them as ``fit_*`` gauges (churn
        normalized to re-assignments per (rep, label) slot) — the
        load-balance family (std/min/max/KL-vs-uniform) is the paper's §4
        balance metric, now observable per round."""
        cfg = self.cfg
        row = {"round": rnd, "seconds": seconds,
               "loss": float(met["loss"]),
               "n_reassigned": int(met["n_reassigned"]),
               "churn": float(met["n_reassigned"])
               / float(cfg.n_reps * cfg.n_labels),
               "load_std": float(met["load_std"])}
        for key in ("grad_norm", "load_min", "load_max", "load_kl"):
            if key in met:
                row[key] = float(met[key])
        if registry is not None:
            registry.counter("fit_rounds_total").inc()
            registry.gauge("fit_round_seconds").set(seconds)
            for key, val in row.items():
                if key in ("round", "seconds"):
                    continue
                registry.gauge(f"fit_{key}" if not key.startswith("fit_")
                               else key).set(val)
        return row

    def build_index(self):
        max_load = int(self.cfg.max_load_slack
                       * max(1, self.cfg.n_labels // self.cfg.n_buckets))
        self.index = PT.build_inverted_index(self.assign, self.cfg.n_buckets,
                                             max_load)

    # -------------------------------------------------------------- query --
    def query(self, queries, m: int = 5, tau: int = 1):
        assert self.index is not None, "fit() or build_index() first"
        return Q.query_index(self.params, self.index, jnp.asarray(queries),
                             m=m, tau=tau, L=self.cfg.n_labels,
                             loss_kind=self.cfg.loss)

    def search(self, queries, base, params: SA.SearchParams | None = None,
               *, cache: SA.PipelineCache | None = None, staged: bool = False,
               m=None, tau=None, k=None, metric=None, mode=None, topC=None):
        """Candidate generation + true-distance re-rank over ``base``.

        Typed path: ``search(queries, base, SearchParams(...))`` ->
        :class:`~repro.core.search_api.SearchResult` (ids [Q, k] with -1
        pad, scores, per-query survivor counts, epoch=0, resolved mode).
        ``base`` is the raw fp32 [L, d] corpus or a
        ``repro.store.QuantizedStore`` over it — encode once with
        ``repro.store.encode(base, "int8")`` and pass
        ``SearchParams(store_dtype="int8")`` for the tiered
        coarse-on-codes + exact-refine rerank (docs/store.md).
        The jitted pipeline comes from ``cache`` (default: the process-wide
        ``search_api.DEFAULT_CACHE``), so equal params + shapes never
        recompile.

        ``staged=True`` serves through the per-stage debug mode (each stage
        separately jitted + fenced, timed into the cache's registry under
        ``serve_stage_seconds{stage=...}``) — bit-identical results, see
        docs/observability.md.

        The bare ``m=/tau=/k=/metric=/mode=/topC=`` kwargs are a deprecated
        shim returning the old ``(ids, n_candidates)`` tuple.
        """
        assert self.index is not None, "fit() or build_index() first"
        if params is None:
            params = SA.params_from_legacy_kwargs(
                "IRLIIndex.search", m=m, tau=tau, k=k, metric=metric,
                mode=mode, topC=topC)
            res = self._search_typed(queries, base, params, cache,
                                     staged=staged)
            return res.ids, res.n_candidates
        SA.check_params("IRLIIndex.search", params)
        if any(v is not None for v in (m, tau, k, metric, mode, topC)):
            raise TypeError("pass either SearchParams or legacy kwargs, "
                            "not both")
        return self._search_typed(queries, base, params, cache, staged=staged)

    def _search_typed(self, queries, base, params: SA.SearchParams,
                      cache: SA.PipelineCache | None, *,
                      staged: bool = False) -> SA.SearchResult:
        cache = cache if cache is not None else SA.DEFAULT_CACHE
        if not hasattr(base, "codes"):        # raw corpus; stores pass as-is
            base = jnp.asarray(base)
        return cache.search(params, self.params, self.index.members,
                            base, jnp.asarray(queries), epoch=self.epoch,
                            staged=staged)

    # ----------------------------------------------------- artifact swap --
    def install_artifact(self, artifact) -> None:
        """Swap in a sealed :class:`repro.artifact.IndexArtifact`.

        The frozen-index flavor of the zero-downtime swap (docs/online.md):
        params/assign/members are replaced wholesale and ``epoch`` jumps to
        the artifact version, so every subsequent ``SearchResult.epoch``
        names exactly the artifact that produced it. Stale versions
        (``version <= self.epoch``) are rejected — installs must move the
        epoch forward. Tombstoned rows are dropped from the rebuilt member
        matrix; the corpus itself is NOT stored here (searches keep passing
        ``base`` explicitly).
        """
        cfg = self.cfg
        md = artifact.meta_dict
        for key, want in (("d", cfg.d), ("n_buckets", cfg.n_buckets),
                          ("n_reps", cfg.n_reps)):
            if key in md and int(md[key]) != int(want):
                raise ValueError(
                    f"artifact {key}={md[key]} != index {key}={want}")
        if int(artifact.version) <= int(self.epoch):
            raise ValueError(
                f"stale artifact: version {artifact.version} <= serving "
                f"epoch {self.epoch}")
        L = cfg.n_labels
        if int(artifact.n_total) < L:
            raise ValueError(
                f"artifact covers {artifact.n_total} labels < index "
                f"n_labels={L}")
        assign = jnp.asarray(artifact.assign)[:, :L]
        max_load = int(artifact.members.shape[-1])
        from repro.artifact import rebuild_members
        members, load = rebuild_members(
            assign, jnp.asarray(artifact.tombstone)[:L],
            B=cfg.n_buckets, max_load=max_load)
        self.params = artifact.params
        self.assign = assign
        self.index = PT.InvertedIndex(members=members, load=load,
                                      max_load=max_load)
        self.epoch = int(artifact.version)

    def as_searcher(self, base, cache: SA.PipelineCache | None = None
                    ) -> SA.Searcher:
        """Bind this frozen index to its corpus as a ``Searcher`` (one-arg
        ``search(queries, params)`` like every other backend)."""
        return SA.as_searcher(
            lambda q, p: self._search_typed(q, base, p, cache))
