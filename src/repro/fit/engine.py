"""Distributed FitEngine — the whole train/re-partition round as ONE
compiled, donatable, mesh-shardable program.

The seed implementation of ``IRLIIndex.fit`` was a host Python loop: one
jitted train step per batch (a host sync each), a fully materialized
[R, L, B] affinity, and a Python loop over the R repetitions for k-choice —
unusable at the paper's "data and model parallel ... ideal for distributed
GPU implementation" scale (§4). The engine replaces it with:

  fit_round(state, idx, w) -> (state', metrics)
    - ``epochs_per_round`` epochs as ONE ``lax.scan`` over pre-permuted
      fixed-size batches (``idx``/``w`` [S, bs] index+weight matrices; the
      tail batch is padded with zero-weight rows, so nothing is dropped and
      nothing biases the gradient). Zero host round-trips inside a round.
    - re-partitioning FUSED into the same compiled call: streaming top-K
      affinity (fit/affinity.py — no [R, L, B] intermediate), vmapped
      power-of-K re-assignment (core/repartition.repartition_topk), and the
      reassignment/load diagnostics.
    - jit with ``donate_argnums=(0,)``: the FitState is double-buffer-free.

  Mesh version: ``shard_map`` over a ("data", "rep") mesh — batch rows split
  over "data" with psum'd grads, the R independent repetitions (params,
  optimizer moments, affinity, k-choice, assign) split over "rep". The
  global-norm grad clip psums squared norms over "rep" so the sharded
  trajectory matches the single-device engine (acceptance-tested with 4
  fake devices in tests/test_fit_engine.py).

Layered above: ``IRLIIndex.fit`` is a thin driver (one host sync per round,
for the paper's convergence check); ``launch/steps.build_irli_fit_parts``
adapts the engine to the fault-tolerant Trainer (auto-resume / atomic
checkpoints / straggler accounting); ``launch/train.py --arch irli`` is the
CLI. docs/fit.md has the full picture.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as PT
from repro.core import repartition as RP
from repro.core.network import scorer_loss_parts
from repro.fit.affinity import (affinity_topk_ann_chunks,
                                affinity_topk_xml_chunks, ann_chunks,
                                chunk_xml_pairs)
from repro.fit.state import FitState
from repro.optim.optimizers import apply_clip, global_norm_sq, make_optimizer

from jax.sharding import PartitionSpec as P


def make_fit_optimizer(cfg):
    """The engine's optimizer. Global-norm clipping moves INTO the engine
    (mesh-aware: the norm is psum'd over "rep" when sharded), so the
    optimizer's own clip is disabled — same math, correct under sharding."""
    return make_optimizer("adamw", lr=cfg.lr, weight_decay=0.0,
                          master_fp32=False, clip_norm=float("inf"))


@dataclasses.dataclass(frozen=True)
class FitData:
    """Device-resident training inputs, prepared once per fit. ANN mode
    carries ``label_vecs`` (Def. 2); XML mode carries the pre-bucketed
    incidence pairs (Def. 1, see fit/affinity.chunk_xml_pairs)."""
    x: jnp.ndarray              # [N, d]
    label_ids: jnp.ndarray      # [N, k] int32
    label_mask: jnp.ndarray     # [N, k] float32
    label_vecs: Any = None      # [L, d] | None  (ANN mode)
    xml_pairs: Any = None       # (points, locs, w) | None (XML mode)
    xml_chunk: int = 0          # label-chunk width the pairs were bucketed at

    @classmethod
    def build(cls, x, label_ids, label_mask=None, label_vecs=None, *,
              n_labels: int = 0, chunk: int = 4096) -> "FitData":
        x = jnp.asarray(x)
        label_ids = jnp.asarray(label_ids, jnp.int32)
        if label_mask is None:
            label_mask = jnp.ones(label_ids.shape, jnp.float32)
        else:
            label_mask = jnp.asarray(label_mask, jnp.float32)
        if label_vecs is not None:
            return cls(x, label_ids, label_mask,
                       label_vecs=jnp.asarray(label_vecs))
        if n_labels <= 0:
            raise ValueError("XML mode (label_vecs=None) needs n_labels > 0 "
                             "to bucket the incidence pairs")
        pts = np.repeat(np.arange(label_ids.shape[0]), label_ids.shape[1])
        labs = np.asarray(label_ids).reshape(-1)
        keep = np.asarray(label_mask).reshape(-1) > 0
        pairs, xml_chunk = chunk_xml_pairs(pts[keep], labs[keep], n_labels,
                                           chunk)
        return cls(x, label_ids, label_mask, xml_pairs=pairs,
                   xml_chunk=xml_chunk)


# a registered pytree (like FitState): shard_map/jit take FitData directly,
# with xml_chunk as static aux data
jax.tree_util.register_pytree_node(
    FitData,
    lambda d: ((d.x, d.label_ids, d.label_mask, d.label_vecs, d.xml_pairs),
               d.xml_chunk),
    lambda chunk, c: FitData(*c, xml_chunk=chunk))


class FitEngine:
    """Builds the compiled fit rounds for one (IRLIConfig, ScorerConfig)."""

    def __init__(self, cfg, scorer_cfg, *, data_axis: str = "data",
                 rep_axis: str = "rep", clip_norm: float = 1.0):
        self.cfg = cfg
        self.scorer_cfg = scorer_cfg
        self.data_axis = data_axis
        self.rep_axis = rep_axis
        self.clip_norm = clip_norm
        self.opt = make_fit_optimizer(cfg)

    # ------------------------------------------------------------ batching -
    def batch_plan(self, n: int) -> tuple[int, int, int]:
        """(steps_per_round, batch_size, batches_per_epoch). The tail batch
        is padded up, never dropped."""
        bs = min(self.cfg.batch_size, n)
        nb = -(-n // bs)
        return self.cfg.epochs_per_round * nb, bs, nb

    def round_batches(self, n: int, data_seed: int, round_idx: int):
        """Pre-permuted fixed-size batches for one round: (idx, w) [S, bs].

        A pure function of (n, data_seed, round_idx) — this is the Trainer's
        deterministic ``batch_fn``, so crash/resume replays the exact batch
        sequence. Padding rows point at row 0 with weight 0: a placement and
        gradient no-op.

        Scale note: idx/w are O(epochs_per_round · n) host-built metadata
        (~5 GB at the full deep1b fit_config) — fine for the in-memory
        regime this engine targets; the 100M-row fit feeds rounds from a
        sharded streaming loader instead of this helper (future work,
        ROADMAP).
        """
        S, bs, nb = self.batch_plan(n)
        E = self.cfg.epochs_per_round
        key = jax.random.fold_in(jax.random.PRNGKey(data_seed), round_idx)
        pad = nb * bs - n
        idx = []
        for e in range(E):
            perm = jax.random.permutation(jax.random.fold_in(key, e), n)
            idx.append(jnp.concatenate(
                [perm.astype(jnp.int32), jnp.zeros(pad, jnp.int32)]))
        idx = jnp.stack(idx).reshape(S, bs)
        w = jnp.concatenate([jnp.ones(n, jnp.float32),
                             jnp.zeros(pad, jnp.float32)])
        w = jnp.broadcast_to(w, (E, nb * bs)).reshape(S, bs)
        return idx, w

    # ----------------------------------------------------------- affinity --
    def _affinity_topk(self, params, data: FitData, data_ax, d_size: int):
        """Streaming top-K affinity for the local reps -> [R_loc, L, K].

        On a mesh, the label-chunk scan is SPLIT over the data axis (each
        data shard scores a contiguous 1/d_size of the chunks, then one
        all_gather of the tiny [R_loc, L/d_size, K] partials reassembles the
        carry) — the same per-chunk computations as the replicated path, so
        results are identical; falls back to replicated compute when the
        chunk count doesn't divide."""
        cfg = self.cfg
        if data.label_vecs is not None:
            L = data.label_vecs.shape[0]
            xs, chunk = ann_chunks(data.label_vecs, self.affinity_chunk)
            reduce = lambda c: affinity_topk_ann_chunks(params, c, cfg.K,
                                                        cfg.loss)
        else:
            L = cfg.n_labels
            xs, chunk = data.xml_pairs, data.xml_chunk
            reduce = lambda c: affinity_topk_xml_chunks(params, data.x, c,
                                                        chunk, cfg.K,
                                                        cfg.loss)
        n_chunks = jax.tree.leaves(xs)[0].shape[0]
        if data_ax and n_chunks % d_size == 0 and d_size > 1:
            loc = n_chunks // d_size
            c0 = jax.lax.axis_index(data_ax) * loc
            xs = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, c0, loc, 0), xs)
            vals, idxs = reduce(xs)
            vals = jax.lax.all_gather(vals, data_ax, axis=1, tiled=True)
            idxs = jax.lax.all_gather(idxs, data_ax, axis=1, tiled=True)
        else:
            vals, idxs = reduce(xs)
        return vals[:, :L], idxs[:, :L]

    # ---------------------------------------------------------- round body -
    def _round_body(self, state: FitState, idx, w, data: FitData, axes):
        cfg, scfg = self.cfg, self.scorer_cfg
        data_ax, rep_ax, d_size = axes if axes is not None else (None, None,
                                                                 1)
        R_glob = scfg.n_reps
        E = cfg.epochs_per_round
        nb = idx.shape[0] // E
        x, lids, lmask = data.x, data.label_ids, data.label_mask
        assign = state.assign                     # fixed through the round

        def psum_data(v):
            return jax.lax.psum(v, data_ax) if data_ax else v

        def psum_rep(v):
            return jax.lax.psum(v, rep_ax) if rep_ax else v

        # ---- train: ONE scan over E * nb fixed-size batches --------------
        def train_step(carry, sw):
            params, opt_state = carry
            sel, wt = sw
            targets = PT.bucket_targets(assign, lids[sel], lmask[sel],
                                        cfg.n_buckets)
            wsum = psum_data(jnp.sum(wt))
            denom = R_glob * jnp.maximum(wsum, 1.0)

            def loss_fn(p):
                s, _ = scorer_loss_parts(p, scfg, x[sel], targets, wt)
                return s / denom

            part, grads = jax.value_and_grad(loss_fn)(params)
            part = psum_data(part)
            grads = psum_data(grads)
            # mesh-aware global-norm clip (the optimizer's disabled built-in,
            # with the squared norm psum'd so it spans ALL reps)
            norm = jnp.sqrt(psum_rep(global_norm_sq(grads)))
            grads = apply_clip(grads, norm, self.clip_norm)
            params, opt_state, _ = self.opt.update(params, grads, opt_state)
            return (params, opt_state), (psum_rep(part), wsum, norm)

        (params, opt_state), (losses, wsums, norms) = jax.lax.scan(
            train_step, (state.params, state.opt_state), (idx, w))
        # per-epoch weighted means (weights = real rows per batch), then the
        # per-round mean of per-epoch means — the loop-variable leak in the
        # old fit recorded only the LAST epoch
        le, we = losses.reshape(E, nb), wsums.reshape(E, nb)
        epoch_loss = jnp.sum(le * we, 1) / jnp.maximum(jnp.sum(we, 1), 1.0)
        round_loss = jnp.mean(epoch_loss)

        # ---- fused re-partition ------------------------------------------
        vals, idxs = self._affinity_topk(params, data, data_ax, d_size)
        next_rng, kr = jax.random.split(state.rng)
        R_loc = assign.shape[0]
        r0 = jax.lax.axis_index(rep_ax) * R_loc if rep_ax else 0
        rep_keys = RP.rep_fold_keys(kr, r0 + jnp.arange(R_loc))
        new_assign = RP.repartition_topk(
            vals, idxs, cfg.n_buckets, cfg.repartition_mode, rep_keys,
            cfg.parallel_slack)
        n_re = psum_rep(jnp.sum(new_assign != assign))
        ld = PT.loads(new_assign, cfg.n_buckets).astype(jnp.float32)
        lstd = psum_rep(jnp.sum(jnp.std(ld, axis=1))) / R_glob
        # the paper's load-balance summary of the NEW partition: bucket
        # min/max across all reps and mean per-rep KL(p || uniform)
        # (0 = perfectly balanced, log B = one hot bucket) — the per-round
        # counterpart of obs.load_balance_stats at serve time
        lmin, lmax = jnp.min(ld), jnp.max(ld)
        if rep_ax:
            lmin = jax.lax.pmin(lmin, rep_ax)
            lmax = jax.lax.pmax(lmax, rep_ax)
        p = ld / jnp.maximum(jnp.sum(ld, axis=1, keepdims=True), 1.0)
        kl = jnp.where(p > 0, p * jnp.log(p * cfg.n_buckets), 0.0)
        lkl = psum_rep(jnp.sum(kl)) / R_glob

        new_state = FitState(params=params, opt_state=opt_state,
                             assign=new_assign, rng=next_rng,
                             round_idx=state.round_idx + 1,
                             epoch_idx=state.epoch_idx + E)
        metrics = {"loss": round_loss, "epoch_loss": epoch_loss,
                   "n_reassigned": n_re, "load_std": lstd,
                   "grad_norm": jnp.mean(norms), "load_min": lmin,
                   "load_max": lmax, "load_kl": lkl}
        return new_state, metrics

    @property
    def affinity_chunk(self) -> int:
        return getattr(self.cfg, "affinity_chunk", 4096)

    # ----------------------------------------------------- compiled rounds -
    def step_fn(self, data: FitData):
        """Un-jitted single-device round over DICT states — the Trainer's
        ``step_fn`` (it jits + donates, and its checkpoint restore yields
        dicts, which FitState round-trips via as_dict/from_dict)."""
        def step(state, batch):
            ns, m = self._round_body(FitState.from_dict(state), batch["idx"],
                                     batch["w"], data, None)
            return ns.as_dict(), m
        return step

    def make_fit_round(self, data: FitData):
        """jitted, donated: fit_round(state, idx, w) -> (state', metrics).

        ``data`` is bound as an ARGUMENT of the compiled round, not closed
        over: jit bakes closed-over arrays into the executable as constants
        (a 2.3 GB program at 2^20 train queries on a v5e, compiled and held
        on the device a second time)."""
        fn = jax.jit(lambda state, idx, w, data: self._round_body(
            state, idx, w, data, None), donate_argnums=(0,))
        return functools.partial(fn, data=data)

    # --------------------------------------------------------- mesh round --
    def _state_specs(self, state: FitState) -> FitState:
        rep = self.rep_axis

        def lead_rep(leaf):
            return P() if leaf.ndim == 0 else P(rep,
                                                *([None] * (leaf.ndim - 1)))

        return FitState(
            params=jax.tree.map(lead_rep, state.params),
            opt_state=jax.tree.map(lead_rep, state.opt_state),
            assign=P(rep, None), rng=P(),
            round_idx=P(), epoch_idx=P())

    def _sharded_round(self, mesh, data: FitData, state: FitState):
        """Un-jitted shard_map fit round on a (data × rep) mesh.

        Batch COLUMNS (rows of each fixed-size batch) split over
        ``data_axis`` with psum'd grads; all leading-R state leaves (params,
        adam moments, assign) split over ``rep_axis``. The training set and
        label payloads arrive replicated, but the affinity label-chunk scan
        is split over ``data_axis`` too (see ``_affinity_topk``), so the
        re-partition sweep is paid once, not d_size times. ``state`` is
        only used as the spec template.
        """
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        d_size, r_size = sizes[self.data_axis], sizes[self.rep_axis]
        assert self.scorer_cfg.n_reps % r_size == 0, \
            f"n_reps={self.scorer_cfg.n_reps} not divisible by " \
            f"{self.rep_axis}={r_size}"
        specs = self._state_specs(state)
        batch_spec = P(None, self.data_axis)
        data_specs = jax.tree.map(lambda _: P(), data)  # replicated payloads
        axes = (self.data_axis, self.rep_axis, d_size)

        def body(state, idx, w, dat):
            return self._round_body(state, idx, w, dat, axes)

        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs, batch_spec, batch_spec, data_specs),
            out_specs=(specs, {"loss": P(), "epoch_loss": P(),
                               "n_reassigned": P(), "load_std": P(),
                               "grad_norm": P(), "load_min": P(),
                               "load_max": P(), "load_kl": P()}),
            check_vma=False)

        def round_fn(state, idx, w, data):
            assert idx.shape[1] % d_size == 0, \
                f"batch size {idx.shape[1]} not divisible by " \
                f"{self.data_axis}={d_size}"
            return mapped(state, idx, w, data)

        return round_fn

    def make_sharded_fit_round(self, mesh, data: FitData, state: FitState):
        """jitted + donated mesh round: fit_round(state, idx, w), with
        ``data`` bound as an argument (see :meth:`make_fit_round`)."""
        fn = jax.jit(self._sharded_round(mesh, data, state),
                     donate_argnums=(0,))
        return functools.partial(fn, data=data)

    def sharded_step_fn(self, mesh, data: FitData, state: FitState):
        """Un-jitted mesh round over dict states (for the Trainer)."""
        round_fn = self._sharded_round(mesh, data, state)

        def step(sd, batch):
            ns, m = round_fn(FitState.from_dict(sd), batch["idx"],
                             batch["w"], data)
            return ns.as_dict(), m
        return step


# ------------------------------------------------------- static contracts --
# The engine's three compiled-program guarantees as registered invariants
# (audited by repro.launch.audit; tests/test_fit_engine.py and
# tests/test_analysis.py assert the same ids):
#   no [R, L, B] dense affinity, FitState donation honored end to end,
#   exactly one trace per round structure, and a bounded mesh collective
#   schedule on the ("data", "rep") path.
from repro.analysis import contracts as _C


def _fit_round_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.fit_round()


def _fit_dense_control():
    from repro.analysis import fixtures as _FX
    return _FX.fit_round_dense_control()


def _fit_sweep_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.fit_round_sweep()


def _sharded_round_fixture():
    from repro.analysis import fixtures as _FX
    return _FX.sharded_fit_round()


_C.register(_C.Contract(
    id="fit.round_no_dense_affinity",
    site="repro.fit.engine.FitEngine.make_fit_round",
    description="the whole compiled train+affinity+re-partition round "
                "never materializes [.., L, B] (the 100M-label guarantee); "
                "the streamed [R, chunk, B] block and the running [R, L, K] "
                "carry must BOTH be sighted (non-vacuity); the seed-style "
                "dense path is the control",
    fixture=_fit_round_fixture,
    checks=[
        _C.forbid_dims("L", "B"),
        _C.require_dims("chunk", "B"),
        _C.require_dims("L", "K"),
    ],
    control=_fit_dense_control,
))

_C.register(_C.Contract(
    id="fit.round_donates_state",
    site="repro.fit.engine.FitEngine.make_fit_round (donate_argnums=(0,))",
    description="every flattened FitState leaf is aliased input->output in "
                "the compiled round (double-buffer-free training); the "
                "control re-jits without donation and must alias nothing",
    fixture=_fit_round_fixture,
    checks=[_C.require_donated()],
))

_C.register(_C.Contract(
    id="fit.round_compiles_once",
    site="repro.fit.engine.FitEngine.make_fit_round",
    description="two rounds over fresh same-structure states trace exactly "
                "once — a retrace means the state pytree or batch "
                "structure drifted between rounds",
    fixture=_fit_sweep_fixture,
    checks=[_C.max_trace_count(1)],
))

_C.register(_C.Contract(
    id="fit.sharded_round_collectives",
    site="repro.fit.engine.FitEngine.make_sharded_fit_round",
    description="the (data x rep) mesh round speaks only all-reduce (grad "
                "psums, scalar diagnostics) and all-gather (split-affinity "
                "reassembly) within a generous byte ceiling — no "
                "all-to-all / reduce-scatter / collective-permute may "
                "appear on the fit path",
    fixture=_sharded_round_fixture,
    checks=[_C.allowed_collectives({
        "all-reduce": 1 << 24, "all-gather": 1 << 24,
    })],
    min_devices=4,
))
