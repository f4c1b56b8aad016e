"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch <id> [--steps N]
        [--devices N] [--ckpt DIR] [--resume]

On this CPU container it runs REDUCED configs (same code paths as the full
configs — the full shapes are exercised via dryrun.py). On a real TPU slice
the same entrypoint binds the production mesh from launch/mesh.py.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (0 = real)")
    ap.add_argument("--metrics-log", default="",
                    help="append per-step metric rows to this JSONL file "
                         "(obs.MetricsLogger, docs/observability.md)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps as S
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.train.trainer import Trainer, TrainerConfig

    rng = np.random.default_rng(0)

    def dump_metrics(out, start=0):
        """Append the run's per-step metric rows (already host scalars via
        Trainer.run's conversion) as one JSONL row per step."""
        if not args.metrics_log:
            return
        from repro import obs
        with obs.MetricsLogger(args.metrics_log) as mlog:
            for i, m in enumerate(out["metrics"]):
                mlog.log(dict(m, arch=args.arch), step=start + i)
        print(f"metrics log -> {args.metrics_log}")

    if args.arch in ("gemma-7b", "yi-6b", "qwen3-4b", "mixtral-8x7b",
                     "llama4-maverick-400b-a17b"):
        from tests.test_smoke_archs import LM_VARIANTS  # reduced configs
        from repro.models.transformer import lm_init
        cfg = LM_VARIANTS[args.arch]
        step, opt = S.build_lm_train_step(cfg, "adamw_nomaster", n_micro=2,
                                          lr=1e-3)

        def init_state():
            params = lm_init(jax.random.PRNGKey(0), cfg)
            return {"params": params, "opt": opt.init(params)}

        def batch_fn(i):
            k = jax.random.PRNGKey(i)
            t = jax.random.randint(k, (4, 64), 0, cfg.vocab)
            return {"tokens": t, "labels": jnp.roll(t, -1, 1)}
    elif args.arch == "irli":
        # the paper's own workload: fit rounds (scan-compiled train +
        # fused re-partition) through the fault-tolerant Trainer, on a
        # (data × rep) mesh when --devices > 1 (docs/fit.md). --steps counts
        # ROUNDS here. Shapes come from configs/irli_deep1b.fit_config.
        from repro.configs.irli_deep1b import fit_config
        from repro.data.synthetic import clustered_ann
        from repro.launch.mesh import make_fit_mesh

        cfg = fit_config(reduced=True)
        data = clustered_ann(n_base=cfg.n_labels, n_queries=32, d=cfg.d,
                             n_clusters=cfg.n_labels // 20, k_gt=10,
                             k_train=20, seed=0)
        n_dev = len(jax.devices())
        mesh = None
        if n_dev > 1:
            # a valid mesh needs rep | n_reps and data | batch_size; prefer
            # using BOTH axes (4 devices -> 2 x 2: data psum + rep sharding)
            valid = [r for r in range(1, n_dev + 1)
                     if n_dev % r == 0 and cfg.n_reps % r == 0
                     and cfg.batch_size % (n_dev // r) == 0]
            if not valid:
                print(f"fit mesh: no (data, rep) split of {n_dev} devices "
                      f"fits n_reps={cfg.n_reps} / batch={cfg.batch_size}; "
                      "running single-device")
            else:
                rep = 2 if 2 in valid else valid[0]
                mesh = make_fit_mesh(n_dev, rep_axis=rep)
                print(f"fit mesh: "
                      f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
        step, init_state, batch_fn = S.build_irli_fit_parts(
            cfg, data.train_queries, data.train_gt, label_vecs=data.base,
            mesh=mesh)
        tr = Trainer(TrainerConfig(total_steps=args.steps,
                                   checkpoint_every=max(2, args.steps // 2)),
                     step, init_state, batch_fn,
                     os.path.join(args.ckpt, args.arch))
        out = tr.run()
        losses = [m["loss"] for m in out["metrics"]]
        if not losses:       # restored a finished run: nothing left to do
            print(f"irli: already complete at round {tr.start_step} "
                  f"(resumed={out['resumed']}); raise --steps to continue")
            return
        moved = [m["n_reassigned"] for m in out["metrics"]]
        print(f"irli: {len(losses)} rounds, loss "
              f"{losses[0]:.4f} -> {losses[-1]:.4f}, "
              f"reassigned {moved[0]:.0f} -> {moved[-1]:.0f}, "
              f"resumed={out['resumed']}")
        dump_metrics(out, tr.start_step)
        return
    elif args.arch == "schnet":
        from repro.models.gnn import SchNetConfig, schnet_init
        from repro.data.synthetic import molecule_batch
        cfg = SchNetConfig(d_in=0, n_types=10, n_out=1, readout="sum",
                           n_rbf=32, d_hidden=32)
        step, opt = S.build_gnn_energy_train(cfg, 16, lr=1e-3)

        def init_state():
            params = schnet_init(jax.random.PRNGKey(0), cfg)
            return {"params": params, "opt": opt.init(params)}

        def batch_fn(i):
            d = molecule_batch(16, 8, 16, seed=i)
            return {k: jnp.asarray(v) for k, v in d.items()}
    else:  # recsys family: dlrm-style CTR on synthetic stream
        import dataclasses as dc
        from repro.models.recsys import DLRMConfig, dlrm_init, dlrm_apply
        cfg = dc.replace(DLRMConfig(), vocab_sizes=(1000, 500, 300),
                         n_sparse=3, n_dense=8, embed_dim=16,
                         bot_mlp=(32, 16), top_mlp=(32, 1))
        params0, offsets = dlrm_init(jax.random.PRNGKey(0), cfg)
        step, opt = S.build_ctr_train_step(
            lambda p, b: dlrm_apply(p, cfg, offsets, b["dense"], b["sparse"]),
            lr=1e-3)

        def init_state():
            return {"params": params0, "opt": opt.init(params0)}

        def batch_fn(i):
            r = np.random.default_rng(i)
            return {"dense": jnp.asarray(r.normal(size=(64, 8)), jnp.float32),
                    "sparse": jnp.asarray(r.integers(0, 300, (64, 3)),
                                          jnp.int32),
                    "label": jnp.asarray(r.integers(0, 2, 64), jnp.float32)}

    tr = Trainer(TrainerConfig(total_steps=args.steps, checkpoint_every=10),
                 step, init_state, batch_fn,
                 os.path.join(args.ckpt, args.arch))
    out = tr.run()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"{args.arch}: {len(losses)} steps, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}, resumed={out['resumed']}")
    dump_metrics(out, tr.start_step)


if __name__ == "__main__":
    main()
