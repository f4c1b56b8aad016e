"""Fit-path benchmark: scan-compiled FitEngine rounds vs the seed host loop.

Three measurements, all written to artifacts/BENCH_fit.json (and printed as
the harness CSV):

  1. wall-clock per train/re-partition round — the seed-style host loop
     (one jitted step per batch + dense [R, L, B] affinity + per-rep Python
     k-choice) vs the engine's single compiled round;
  2. peak affinity-path intermediate bytes, measured by walking the traced
     jaxpr of each re-partition path (dense materializes [R, L, B]; the
     streaming reducer's largest block is [R, chunk, B] / the [R, L, K]
     carry);
  3. the same engine round on 1 vs 4 fake host devices on a
     ("data", "rep") mesh, each in a child process pinned to the CPU (the
     device count is fixed when jax starts, and a CPU child never competes
     with this process for a chip) — scaling sanity on CPU, the real win is
     on a TPU slice. A child that fails fails the run.

    PYTHONPATH=src python -m benchmarks.bench_fit [--toy]
"""
import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as PT
from repro.core import repartition as RP
from repro.core.index import IRLIConfig
from repro.core.network import ScorerConfig, scorer_init, scorer_loss
from repro.data.synthetic import clustered_ann
from repro.fit import FitData, FitEngine, FitState, affinity_topk_ann
from repro.optim.optimizers import make_optimizer

from repro.analysis.jaxpr import peak_intermediate_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(ROOT, "artifacts")


# ------------------------------------------------------ host-loop baseline --
def make_hostloop_round(cfg, scfg, opt):
    """The seed IRLIIndex.fit inner loop, verbatim semantics: ONE jitted
    per-batch step cached across rounds (as the seed cached
    ``self._train_step``) with a host sync each batch, dense [R, L, B]
    affinity, Python loop over reps for k-choice."""

    @jax.jit
    def train_step(params, opt_state, xb, ib, mb, assign):
        targets = PT.bucket_targets(assign, ib, mb, cfg.n_buckets)
        loss, grads = jax.value_and_grad(
            lambda p: scorer_loss(p, scfg, xb, targets))(params)
        params, opt_state, _ = opt.update(params, grads, opt_state)
        return params, opt_state, loss

    def hostloop_round(params, opt_state, assign, x, ids, mask, lv, key):
        n, bs = x.shape[0], min(cfg.batch_size, x.shape[0])
        for ep in range(cfg.epochs_per_round):
            key, ke = jax.random.split(key)
            perm = jax.random.permutation(ke, n)
            for s in range(0, n - bs + 1, bs):
                sel = perm[s:s + bs]
                params, opt_state, loss = train_step(
                    params, opt_state, x[sel], ids[sel], mask[sel], assign)
                float(loss)                   # the seed's per-batch sync
        aff = RP.affinity_ann(params, lv, cfg.loss)
        key, kr = jax.random.split(key)
        vals, idxs = jax.lax.top_k(aff, cfg.K)
        outs = [RP.kchoice_exact(idxs[r], cfg.n_buckets,
                                 jax.random.fold_in(kr, r))
                for r in range(cfg.n_reps)]
        return params, opt_state, jnp.stack(outs), key

    return hostloop_round


def _time_rounds(fn, n_rounds):
    fn()                                      # warmup / compile
    t0 = time.time()
    for _ in range(n_rounds):
        fn()
    return (time.time() - t0) / n_rounds


def _device_round(ndev: int, n: int, rounds: int) -> dict:
    """The same fit on ``ndev`` devices of this process (one device: no
    mesh; more: a ("data", "rep") mesh)."""
    from repro.core.index import IRLIIndex
    from repro.launch.mesh import make_fit_mesh
    if len(jax.devices()) < ndev:
        raise RuntimeError(f"{ndev} devices requested, "
                           f"{len(jax.devices())} visible")
    data = clustered_ann(n_base=n, n_queries=16, d=16, n_clusters=n // 20,
                         k_gt=10, k_train=20, seed=0)
    cfg = IRLIConfig(d=16, n_labels=n, n_buckets=64, n_reps=4, d_hidden=64,
                     K=8, rounds=rounds, epochs_per_round=2, batch_size=512,
                     lr=2e-3, affinity_chunk=512, seed=1)
    mesh = make_fit_mesh(ndev, rep_axis=2) if ndev > 1 else None
    idx = IRLIIndex(cfg)
    t0 = time.time()
    stats = idx.fit(data.train_queries, data.train_gt, label_vecs=data.base,
                    mesh=mesh)
    return {"devices": ndev,
            "s_per_round": (time.time() - t0) / len(stats.round_idx),
            "loss": stats.train_loss}


def _device_round_child(ndev: int, n: int, rounds: int) -> dict:
    """:func:`_device_round` in a child process on ``ndev`` fake CPU
    devices; a non-zero exit raises."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={ndev}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), ROOT,
                    env.get("PYTHONPATH")) if p)
    r = subprocess.run([sys.executable, "-m", "benchmarks.bench_fit",
                        "--device-round", str(ndev), str(n), str(rounds)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"devices={ndev} round failed (exit "
                           f"{r.returncode}): {r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(csv=True, toy=False):
    n = 1024 if toy else 4096
    rounds = 1 if toy else 2
    cfg = IRLIConfig(d=16, n_labels=n, n_buckets=64, n_reps=4, d_hidden=64,
                     K=8, rounds=rounds, epochs_per_round=2, batch_size=512,
                     lr=2e-3, affinity_chunk=512, seed=1)
    scfg = ScorerConfig(d_in=cfg.d, d_hidden=cfg.d_hidden,
                        n_buckets=cfg.n_buckets, n_reps=cfg.n_reps,
                        loss=cfg.loss)
    data = clustered_ann(n_base=n, n_queries=16, d=16, n_clusters=n // 20,
                         k_gt=10, k_train=20, seed=0)
    x = jnp.asarray(data.train_queries)
    ids = jnp.asarray(data.train_gt, jnp.int32)
    mask = jnp.ones(ids.shape, jnp.float32)
    lv = jnp.asarray(data.base)
    params = scorer_init(jax.random.PRNGKey(0), scfg)

    rows, rec = [], {}

    # --- host loop (seed behavior) --------------------------------------
    opt_host = make_optimizer("adamw", lr=cfg.lr, weight_decay=0.0,
                              master_fp32=False)
    hstate = {"params": jax.tree.map(jnp.copy, params)}
    hstate["opt"] = opt_host.init(hstate["params"])
    hstate["assign"] = PT.hash_init(n, cfg.n_buckets, cfg.n_reps, cfg.seed)
    hstate["key"] = jax.random.PRNGKey(cfg.seed)

    hostloop_round = make_hostloop_round(cfg, scfg, opt_host)

    def host_round():
        hstate["params"], hstate["opt"], hstate["assign"], hstate["key"] = \
            hostloop_round(hstate["params"], hstate["opt"], hstate["assign"],
                           x, ids, mask, lv, hstate["key"])

    host_s = _time_rounds(host_round, rounds)

    # --- scan-compiled engine round -------------------------------------
    eng = FitEngine(cfg, scfg)
    fdata = FitData.build(x, ids, label_vecs=lv, n_labels=n,
                          chunk=cfg.affinity_chunk)
    round_fn = eng.make_fit_round(fdata)
    box = {"state": FitState.create(
        jax.tree.map(jnp.copy, params), eng.opt.init(params),
        PT.hash_init(n, cfg.n_buckets, cfg.n_reps, cfg.seed),
        jax.random.PRNGKey(cfg.seed)), "rnd": 0}

    def engine_round():
        bidx, bw = eng.round_batches(n, cfg.seed, box["rnd"])
        box["state"], met = round_fn(box["state"], bidx, bw)
        jax.block_until_ready(met["loss"])
        box["rnd"] += 1

    engine_s = _time_rounds(engine_round, rounds)

    rows.append(("fit/host_loop_round", host_s * 1e6,
                 f"n={n};R={cfg.n_reps};B={cfg.n_buckets}"))
    rows.append(("fit/engine_round", engine_s * 1e6,
                 f"speedup={host_s / engine_s:.2f}x"))
    rec.update(n=n, host_s_per_round=host_s, engine_s_per_round=engine_s,
               speedup=host_s / engine_s)

    # --- peak affinity bytes (jaxpr walk) -------------------------------
    dense_fn = lambda p: RP.repartition(RP.affinity_ann(p, lv, cfg.loss),
                                        cfg.K, cfg.n_buckets, "exact",
                                        jax.random.PRNGKey(0))
    stream_fn = lambda p: RP.repartition_topk(
        *affinity_topk_ann(p, lv, cfg.K, cfg.loss, cfg.affinity_chunk),
        cfg.n_buckets, "exact",
        RP.rep_fold_keys(jax.random.PRNGKey(0), jnp.arange(cfg.n_reps)))
    dense_b = peak_intermediate_bytes(dense_fn, params)
    stream_b = peak_intermediate_bytes(stream_fn, params)
    rows.append(("fit/affinity_peak_dense_bytes", dense_b,
                 f"[R,L,B]={cfg.n_reps * n * cfg.n_buckets * 4}"))
    rows.append(("fit/affinity_peak_stream_bytes", stream_b,
                 f"ratio={dense_b / max(stream_b, 1):.1f}x"))
    rec.update(affinity_peak_dense_bytes=dense_b,
               affinity_peak_stream_bytes=stream_b)

    # --- 1 vs 4 fake CPU devices (a child each) --------------------------
    if not toy:
        for ndev in (1, 4):
            out = _device_round_child(ndev, n, rounds)
            rows.append((f"fit/engine_round_devices={ndev}",
                         out["s_per_round"] * 1e6,
                         f"loss_end={out['loss'][-1]:.3f}"))
            rec[f"s_per_round_devices{ndev}"] = out["s_per_round"]

    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "BENCH_fit.json"), "w") as f:
        json.dump(rec, f, indent=1)

    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.0f},{derived}")
    if not toy:     # --toy shapes would pollute the longitudinal baseline
        from benchmarks import trajectory
        trajectory.record("fit", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--toy", action="store_true",
                    help="CI smoke: small shapes, no 1-vs-4 device rounds")
    ap.add_argument("--device-round", nargs=3, type=int,
                    metavar=("NDEV", "N", "ROUNDS"),
                    help="run one 1-vs-4 leg here and print it as JSON")
    args = ap.parse_args()
    if args.device_round:
        print(json.dumps(_device_round(*args.device_round)))
    else:
        run(toy=args.toy)
