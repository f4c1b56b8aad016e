"""FitEngine acceptance: streaming top-K affinity == dense (ANN + XML) with
a jaxpr-walk proof that the compiled affinity+re-partition round never
materializes [R, L, B] (dense positive control, same style as the
store/compact proofs); vmapped repartition == the old per-rep loop;
lexicographic k-choice tie-break at large loads; tail-batch gradient
contribution; per-round loss = mean of per-epoch means; FitState checkpoint
round-trip; crash/resume bitwise determinism through the Trainer; and the
(data × rep) sharded engine matching the single-device engine (subprocess
with 4 fake host devices)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import repartition as RP
from repro.core.index import IRLIConfig, IRLIIndex
from repro.core.network import ScorerConfig, scorer_init
from repro.fit import (FitData, FitEngine, FitState, affinity_topk_ann,
                       affinity_topk_xml, chunk_xml_pairs)

D = 16


def _cfg(**kw):
    base = dict(d=D, n_labels=300, n_buckets=24, n_reps=3, d_hidden=32,
                K=4, rounds=2, epochs_per_round=3, batch_size=64, lr=2e-3,
                affinity_chunk=64, seed=0)
    base.update(kw)
    return IRLIConfig(**base)


def _scorer(cfg, seed=0):
    scfg = ScorerConfig(d_in=cfg.d, d_hidden=cfg.d_hidden,
                        n_buckets=cfg.n_buckets, n_reps=cfg.n_reps,
                        loss=cfg.loss)
    return scfg, scorer_init(jax.random.PRNGKey(seed), scfg)


def _ann_data(cfg, n=150, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, cfg.d)).astype(np.float32)
    ids = rng.integers(0, cfg.n_labels, (n, 5)).astype(np.int32)
    lv = rng.normal(size=(cfg.n_labels, cfg.d)).astype(np.float32)
    return FitData.build(x, ids, label_vecs=lv, n_labels=cfg.n_labels,
                         chunk=cfg.affinity_chunk)


# ------------------------------------------------- streaming affinity -------
def test_affinity_ann_streaming_matches_dense():
    cfg = _cfg(n_labels=301)          # non-multiple of chunk: padded tail
    _, params = _scorer(cfg)
    lv = jnp.asarray(np.random.default_rng(1).normal(size=(301, D)),
                     jnp.float32)
    vals, idxs = affinity_topk_ann(params, lv, cfg.K, cfg.loss, chunk=64)
    dense = RP.affinity_ann(params, lv, cfg.loss)
    dv, di = jax.lax.top_k(dense, cfg.K)
    assert vals.shape == (cfg.n_reps, 301, cfg.K)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(dv),
                               rtol=1e-5, atol=1e-6)
    assert (np.asarray(idxs) == np.asarray(di)).mean() > 0.99

def test_affinity_xml_streaming_matches_dense():
    cfg = _cfg(n_labels=100)
    _, params = _scorer(cfg)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(120, D)), jnp.float32)
    pts = np.repeat(np.arange(120), 3)
    labs = rng.integers(0, 100, 360)
    pairs, chunk = chunk_xml_pairs(pts, labs, 100, 32)
    vals, idxs = affinity_topk_xml(params, x, pairs, 100, cfg.K, cfg.loss,
                                   chunk)
    dense = RP.affinity_xml(params, x, jnp.asarray(pts, jnp.int32),
                            jnp.asarray(labs, jnp.int32), 100, cfg.loss)
    dv, di = jax.lax.top_k(dense, cfg.K)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(dv),
                               rtol=1e-4, atol=1e-5)
    assert (np.asarray(idxs) == np.asarray(di)).mean() > 0.99


# ----------------------------------------------------- no [R, L, B] proof ---
def test_fit_round_never_materializes_RLB():
    """Acceptance: the WHOLE compiled train+affinity+re-partition round
    contains no [.., L, B] intermediate — the 100M-label fit guarantee —
    plus non-vacuity (the streamed [R, chunk, B] block and the [R, L, K]
    carry ARE seen). Proven by the contract registered beside
    repro.fit.engine; the seed-style dense path is its built-in control."""
    from repro import analysis
    analysis.load_all()
    report = analysis.audit("fit.round_no_dense_affinity")
    assert report.passed, report.to_dict()
    assert report.control_ok, report.control_detail


def test_production_streaming_affinity_bytes():
    from repro.configs.irli_deep1b import fit_affinity_bytes
    acct = fit_affinity_bytes()
    assert acct["ratio"] >= 100, acct  # dense [R,L,B] >= 100x the live set


# --------------------------------------------------- vmapped re-partition ---
@pytest.mark.parametrize("mode", ["exact", "parallel"])
def test_repartition_vmap_matches_per_rep_loop(mode):
    rng = np.random.default_rng(3)
    R, L, B, K = 3, 120, 16, 4
    aff = jnp.asarray(rng.random((R, L, B)), jnp.float32)
    key = jax.random.PRNGKey(7)
    got = RP.repartition(aff, K, B, mode, key, slack=1.3)
    vals, idxs = jax.lax.top_k(aff, K)
    want = []
    for r in range(R):       # the old per-rep Python loop, verbatim
        if mode == "exact":
            want.append(RP.kchoice_exact(idxs[r], B,
                                         jax.random.fold_in(key, r)))
        else:
            want.append(RP.kchoice_parallel(vals[r], idxs[r], B, slack=1.3))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack(want)))


# ------------------------------------------------- k-choice tie-breaking ----
def test_kchoice_tiebreak_survives_large_loads():
    """Lexicographic (load, choice-rank) argmin: adding a huge constant to
    every bucket load must not change a single placement. The old
    ``load + arange(K)*1e-7`` tie-break is absorbed by float32 well below
    this magnitude (the 100M-row regime of the satellite)."""
    rng = np.random.default_rng(4)
    L, B, K = 64, 8, 4
    topk = jnp.asarray(
        np.stack([rng.permutation(B)[:K] for _ in range(L)]).astype(np.int32))
    small = jnp.asarray(rng.integers(0, 5, B), jnp.float32)
    base = float(2 ** 23)      # integer spacing still exact, 1e-7 absorbed
    a_small = np.asarray(RP.kchoice_exact(topk, B, load0=small))
    a_big = np.asarray(RP.kchoice_exact(topk, B, load0=small + base))
    np.testing.assert_array_equal(a_small, a_big)
    # oracle: sequential least-loaded with first-of-ties (= highest affinity)
    load = np.asarray(small + base, np.float64)
    for l in range(L):
        cand = np.asarray(topk[l])
        j = int(np.flatnonzero(load[cand] == load[cand].min())[0])
        assert a_big[l] == cand[j], l
        load[cand[j]] += 1


def test_kchoice_tiebreak_fractional_loads():
    """A strictly-less-loaded later-rank bucket must win even when the load
    gap is below the old epsilon (fractional streaming weights): with
    loads (0.25, 0.25 - 6e-8) the epsilon version picks rank 0."""
    topk = jnp.asarray([[0, 1]], jnp.int32)
    load0 = jnp.asarray([0.25, np.float32(0.25) - np.float32(6e-8)])
    assert int(RP.kchoice_exact(topk, 2, load0=load0)[0]) == 1


# -------------------------------------------------------- batching fixes ----
def test_tail_batch_contributes_gradient():
    """n = batch_size + 1: the 1-point remainder must still train (the seed
    ``range(0, n - bs + 1, bs)`` silently dropped it)."""
    cfg = _cfg(n_labels=64, batch_size=64, rounds=1, epochs_per_round=1)
    scfg, params = _scorer(cfg)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(65, D)).astype(np.float32)
    ids = rng.integers(0, 64, (65, 4)).astype(np.int32)
    lv = rng.normal(size=(64, D)).astype(np.float32)
    eng = FitEngine(cfg, scfg)
    data = FitData.build(x, ids, label_vecs=lv, n_labels=64, chunk=64)
    round_fn = eng.make_fit_round(data)

    def one_round(i, w):
        p0 = jax.tree.map(jnp.copy, params)     # round_fn donates its state
        state = FitState.create(
            p0, eng.opt.init(p0),
            np.zeros((cfg.n_reps, 64), np.int32), jax.random.PRNGKey(0))
        out, _ = round_fn(state, i, w)
        return out.params

    # weights: every real row carries weight 1, pad rows 0
    i, w = eng.round_batches(65, 0, 0)
    assert i.shape == (2, 64) and float(jnp.sum(w)) == 65.0
    p_full = one_round(i, w)
    # zero the weight of the tail batch's single REAL row (the one the seed
    # loop dropped): the outcome must change, i.e. that row carries gradient
    real_tail = int(np.argmax(np.asarray(w[1]) > 0))
    p_drop = one_round(i, w.at[1, real_tail].set(0.0))
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_drop))]
    assert max(diffs) > 0, "tail batch contributed no gradient"
    # and zero-weight PAD rows are inert: repointing one at a different row
    # changes nothing, bitwise
    pad_slot = int(np.argmin(np.asarray(w[1])))
    p_repoint = one_round(i.at[1, pad_slot].set(17), w)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_repoint)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_loss_is_mean_of_epoch_means():
    """FitStats.train_loss must be the per-round mean of per-epoch means —
    the seed recorded only the LAST epoch (loop-variable leak)."""
    cfg = _cfg(n_labels=200, rounds=2, epochs_per_round=3)
    rng = np.random.default_rng(6)
    idx = IRLIIndex(cfg)
    stats = idx.fit(rng.normal(size=(150, D)).astype(np.float32),
                    rng.integers(0, 200, (150, 5)).astype(np.int32),
                    label_vecs=rng.normal(size=(200, D)).astype(np.float32))
    for rnd, (tl, el) in enumerate(zip(stats.train_loss, stats.epoch_loss)):
        assert len(el) == 3
        assert tl == pytest.approx(float(np.mean(el)), rel=1e-5)
        # the loss moves across epochs, so mean-of-epochs != last epoch:
        # recording the leak would fail here
        assert tl != pytest.approx(el[-1], rel=1e-6), (rnd, tl, el)


# ------------------------------------------------ checkpoint + resume -------
def test_fitstate_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.checkpointer import CheckpointManager
    cfg = _cfg()
    scfg, params = _scorer(cfg)
    eng = FitEngine(cfg, scfg)
    state = FitState.create(params, eng.opt.init(params),
                            np.zeros((cfg.n_reps, cfg.n_labels), np.int32),
                            jax.random.PRNGKey(3))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state.as_dict())
    tree, _ = mgr.restore(0)
    back = FitState.from_dict(jax.tree.map(jnp.asarray, tree))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_crash_resume_bitwise_identical(tmp_path):
    """Kill a fit mid-run via fail_at_step, restore, and the final assign
    and loss trajectory are bitwise-identical to an uninterrupted run."""
    from repro.launch.steps import build_irli_fit_parts
    from repro.train.trainer import SimulatedFailure, Trainer, TrainerConfig

    cfg = _cfg(n_labels=128, n_buckets=16, rounds=3, epochs_per_round=2,
               batch_size=50)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(110, D)).astype(np.float32)
    ids = rng.integers(0, 128, (110, 4)).astype(np.int32)
    lv = rng.normal(size=(128, D)).astype(np.float32)

    def trainer(dir_, fail_at=None):
        parts = build_irli_fit_parts(cfg, x, ids, label_vecs=lv)
        tcfg = TrainerConfig(total_steps=3, checkpoint_every=2,
                             fail_at_step=fail_at)
        return Trainer(tcfg, *parts, str(tmp_path / dir_))

    ref = trainer("ref")
    ref_out = ref.run()

    with pytest.raises(SimulatedFailure):
        trainer("crash", fail_at=2).run()
    tr2 = trainer("crash")
    assert tr2.resumed and tr2.start_step == 2
    out2 = tr2.run()

    np.testing.assert_array_equal(np.asarray(ref.state["assign"]),
                                  np.asarray(tr2.state["assign"]))
    for a, b in zip(jax.tree.leaves(ref.state["params"]),
                    jax.tree.leaves(tr2.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ref_losses = [m["loss"] for m in ref_out["metrics"]]
    res_losses = [m["loss"] for m in out2["metrics"]]
    assert ref_losses[2:] == res_losses   # the re-run rounds, bit-identical


# ------------------------------------------------- (data × rep) sharding ----
_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from repro.core.index import IRLIConfig, IRLIIndex
    from repro.data.synthetic import clustered_ann
    from repro.launch.mesh import make_fit_mesh

    data = clustered_ann(n_base=600, n_queries=20, d=16, n_clusters=30,
                         k_gt=10, k_train=20, seed=0)
    # affinity_chunk=150 -> 4 label chunks: divisible by the data axis (2),
    # so the subprocess exercises the data-split affinity + all_gather path
    cfg = IRLIConfig(d=16, n_labels=600, n_buckets=32, n_reps=4, d_hidden=32,
                     K=4, rounds=2, epochs_per_round=2, batch_size=200,
                     lr=2e-3, affinity_chunk=150, seed=1)

    one = IRLIIndex(cfg)
    s1 = one.fit(data.train_queries, data.train_gt, label_vecs=data.base)

    mesh = make_fit_mesh(4, rep_axis=2)        # ("data", "rep") = (2, 2)
    assert mesh.axis_names == ("data", "rep")
    four = IRLIIndex(cfg)
    s4 = four.fit(data.train_queries, data.train_gt, label_vecs=data.base,
                  mesh=mesh)

    a1, a4 = np.asarray(one.assign), np.asarray(four.assign)
    print(json.dumps({
        "loss1": s1.train_loss, "loss4": s4.train_loss,
        "epoch1": s1.epoch_loss, "epoch4": s4.epoch_loss,
        "assign_match": float((a1 == a4).mean()),
        "re1": s1.n_reassigned, "re4": s4.n_reassigned,
        "lstd1": s1.load_std, "lstd4": s4.load_std}))
""")


def test_sharded_fit_matches_single_device():
    """Acceptance: a 4-fake-device ("data", "rep") fit produces assign/loss
    trajectories matching the single-device engine within test tolerance."""
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(out["loss1"], out["loss4"], rtol=1e-4)
    np.testing.assert_allclose(np.concatenate(out["epoch1"]),
                               np.concatenate(out["epoch4"]), rtol=1e-4)
    np.testing.assert_allclose(out["lstd1"], out["lstd4"], rtol=0.05)
    assert out["assign_match"] > 0.98, out
    assert out["re1"] == out["re4"] or all(
        abs(a - b) < 0.02 * 600 * 4 for a, b in zip(out["re1"], out["re4"]))


_SHARDED_INDEX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import partition as PT
    from repro.launch.mesh import make_fit_mesh

    assign = PT.hash_init(500, 16, 4, seed=3)
    want = PT.build_inverted_index(assign, 16, 70)
    mesh = make_fit_mesh(4, rep_axis=2)
    explicit = jax.make_mesh((2, 2), ("data", "rep"))   # jax's default axes
    for m in (mesh, explicit):
        sharded = jax.device_put(assign, NamedSharding(m, P("rep", None)))
        got = PT.build_inverted_index(sharded, 16, 70)
        np.testing.assert_array_equal(np.asarray(got.members),
                                      np.asarray(want.members))
        np.testing.assert_array_equal(np.asarray(got.load),
                                      np.asarray(want.load))
    print("ok")
""")


def test_fit_reports_dropped_slots():
    """A member width below the bucket loads loses (rep, label) slots: the
    fit counts them in FitStats and a registry gauge, and warns."""
    from repro import obs
    cfg = _cfg(max_load_slack=0.5, rounds=1)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, D)).astype(np.float32)
    gt = rng.integers(0, cfg.n_labels, (128, 4)).astype(np.int32)
    reg = obs.MetricRegistry()
    idx = IRLIIndex(cfg)
    with pytest.warns(RuntimeWarning, match="slots overflow"):
        stats = idx.fit(x, gt, registry=reg)
    held = int((np.asarray(idx.index.members) >= 0).sum())
    assert stats.dropped_slots == idx.index.dropped > 0
    assert held + stats.dropped_slots == cfg.n_reps * cfg.n_labels
    assert (reg.snapshot()["fit_index_dropped_slots"]["value"]
            == stats.dropped_slots)


def test_build_inverted_index_accepts_rep_sharded_assign():
    """The member-matrix rebuild after a mesh fit takes an assign sharded
    over "rep" on a 4-device mesh — Auto axes (make_fit_mesh) or jax's
    default Explicit ones — outside any mesh context, and equals the
    single-device rebuild."""
    r = subprocess.run([sys.executable, "-c", _SHARDED_INDEX_SCRIPT],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("ok")
