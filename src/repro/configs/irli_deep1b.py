"""irli-deep1b — the PAPER'S OWN production configuration (§5.3) on the
assigned meshes: 100M (padded to 2^27 ≈ 134M) 96-d vectors, B=20000 buckets,
R=32 scorer repetitions, hidden 1024.

Mapping (DESIGN §3/§5): the paper's P=8 corpus shards generalize to the full
("pod","data") product; the R=32 reps ride the stacked-param leading axis
(sharded over "model" -> 2 reps/chip column). Cells:

  train_scorers   scorer BCE train step on 1M-query batches (train)
  serve_query     sharded multiprobe search, batch 4096 queries, int8
                  tiered vector store (serve) — the store's first consumer

These two extra cells put the paper's actual workload on the production mesh
alongside the 40 assigned-architecture cells. ``fit_config()`` additionally
carries the FitEngine hyperparameters (docs/fit.md) behind
``launch/train.py --arch irli`` — full-size for the production mesh,
``reduced=True`` for the CPU container / CI fit-smoke — and
``fit_affinity_bytes()`` pins the streaming-vs-dense affinity accounting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchDef, CellDef, dp, sds
from repro.core.network import ScorerConfig, scorer_init
from repro.launch import steps as S
from repro.models.module import ShardRules

D = 96
B_BUCKETS = 20000
R = 32
HIDDEN = 1024
N_CORPUS = 1 << 27           # 134,217,728 (assigned 100M padded to 2^27)
K_NEIGH = 100                 # paper: 100 exact NNs as labels
MAX_LOAD = 2 * (N_CORPUS // (256 * B_BUCKETS))  # per-shard bucket load bound
# quantized tiered store (docs/store.md): fp32 base vectors are 2^27·96·4
# ≈ 51.5 GB — unservable; int8 block-scaled codes + per-32-block scales are
# ~3.7x smaller and the serve cell declares THEM as its vector payload
STORE_DTYPE = "int8"
STORE_BLOCK = 32
N_SCALE_BLOCKS = D // STORE_BLOCK

# serve knobs of the serve_query cell: top-m probe width, frequency
# threshold tau, final top-k
SERVE_M, SERVE_TAU, SERVE_K = 5, 2, 10

SCORER_CFG = ScorerConfig(d_in=D, d_hidden=HIDDEN, n_buckets=B_BUCKETS,
                          n_reps=R, loss="softmax_bce")

# fit-engine hyperparameters (docs/fit.md): the paper's Alg. 1 alternation
FIT_K = 10                    # power-of-K re-partition choices
FIT_ROUNDS = 5
FIT_EPOCHS_PER_ROUND = 5
FIT_BATCH = 1 << 15           # matches the train_scorers cell
FIT_AFFINITY_CHUNK = 1 << 16  # label-chunk width of the streaming top-K


def fit_config(*, reduced: bool = False):
    """The IRLIConfig behind ``launch/train.py --arch irli``.

    ``reduced=True`` shrinks every shape for the CPU container / CI
    fit-smoke while keeping the identical code paths (scan-compiled rounds,
    streaming affinity, (data × rep) shard_map); the full-size config is
    what the production mesh trains."""
    from repro.core.index import IRLIConfig
    if reduced:
        return IRLIConfig(d=16, n_labels=500, n_buckets=32, n_reps=4,
                          d_hidden=32, K=4, rounds=FIT_ROUNDS,
                          epochs_per_round=2, batch_size=128, lr=2e-3,
                          affinity_chunk=128, seed=0)
    return IRLIConfig(d=D, n_labels=N_CORPUS, n_buckets=B_BUCKETS, n_reps=R,
                      d_hidden=HIDDEN, K=FIT_K, rounds=FIT_ROUNDS,
                      epochs_per_round=FIT_EPOCHS_PER_ROUND,
                      batch_size=FIT_BATCH, lr=1e-3,
                      affinity_chunk=FIT_AFFINITY_CHUNK, seed=0)


def fit_affinity_bytes(chunk: int = FIT_AFFINITY_CHUNK) -> dict:
    """Byte accounting of the re-partition affinity at paper scale: the
    dense [R, L, B] table the seed code materialized vs the streaming
    reducer's live set (one [R, chunk, B] block + the running [R, L, K]
    carry). Asserted >= 100x apart in tests/test_fit_engine.py so the
    config can't silently regress to the dense path."""
    dense = R * N_CORPUS * B_BUCKETS * 4
    streaming = R * chunk * B_BUCKETS * 4 + R * N_CORPUS * FIT_K * (4 + 4)
    return {"dense_RLB": dense, "streaming": streaming,
            "ratio": dense / streaming}


def _abstract_params():
    return jax.eval_shape(
        lambda: scorer_init(jax.random.PRNGKey(0), SCORER_CFG))


def _rules():
    # R axis over "model": w1 [R,d,H], w2 [R,H,B]
    return ShardRules([
        (r"w1", P("model", None, None)),
        (r"b1", P("model", None)),
        (r"w2", P("model", None, None)),
        (r"b2", P("model", None)),
    ])


def _train_cell() -> CellDef:
    # 32k queries/step: the BCE targets are [R, batch, B] (~84 GB fp32 global
    # at 32k) — streamed minibatches exactly as the paper trains (10M total).
    BATCH = 1 << 15

    def inputs(mesh):
        return {"x": sds((BATCH, D)),
                "label_ids": sds((BATCH, K_NEIGH), jnp.int32),
                "label_mask": sds((BATCH, K_NEIGH)),
                "assign": sds((R, N_CORPUS), jnp.int32)}

    def in_specs(mesh):
        ax = dp(mesh)
        return {"x": P(ax, None), "label_ids": P(ax, None),
                "label_mask": P(ax, None),
                "assign": P("model", ("data",))}

    return CellDef(
        kind="train", inputs=inputs, in_specs=in_specs,
        step=lambda: S.build_irli_train_step(SCORER_CFG, B_BUCKETS)[0])


def _mesh_size(mesh) -> int:
    out = 1
    for s in mesh.devices.shape:
        out *= s
    return out


def serve_store_bytes(n_shards: int) -> dict:
    """Per-shard byte accounting of the serve cell's vector payload —
    asserted by launch/dryrun.py against the compiled cell's argument
    sizes, so the config can't silently regress to fp32 vectors."""
    l_loc = N_CORPUS // n_shards
    return {
        "l_loc": l_loc,
        "fp32_per_shard": l_loc * D * 4,
        "int8_per_shard": l_loc * D * 1 + l_loc * N_SCALE_BLOCKS * 4,
        "members_per_shard": R * B_BUCKETS
        * (2 * max(1, l_loc // B_BUCKETS)) * 4,
    }


def _serve_cell() -> CellDef:
    QBATCH = 4096

    def params_for(mesh):
        n_shards = _mesh_size(mesh)
        l_loc = N_CORPUS // n_shards
        max_load = 2 * max(1, l_loc // B_BUCKETS)
        return {
            "scorer": _abstract_params(),
            "members": sds((n_shards, R, B_BUCKETS, max_load), jnp.int32),
            # the int8 tiered store IS the declared vector payload: no fp32
            # base array exists anywhere in the serve cell
            "base_codes": sds((n_shards, l_loc, D), jnp.int8),
            "base_scales": sds((n_shards, l_loc, N_SCALE_BLOCKS)),
        }

    def param_specs(mesh, params_sds):
        axes = tuple(mesh.axis_names)
        return {
            "scorer": jax.tree.map(lambda _: P(), params_sds["scorer"]),
            "members": P(axes, None, None, None),
            "base_codes": P(axes, None, None),
            "base_scales": P(axes, None, None),
        }

    return CellDef(
        kind="serve",
        inputs=lambda mesh: {"queries": sds((QBATCH, D))},
        in_specs=lambda mesh: {"queries": P()},
        params=params_for, param_specs=param_specs,
        step=lambda mesh: S.build_irli_serve(
            mesh, m=SERVE_M, tau=SERVE_TAU, k=SERVE_K,
            store_dtype=STORE_DTYPE,
            store_block=STORE_BLOCK),
        step_with_mesh=True,
        note="every chip = one paper node; sorted-frequency candidate path; "
             "int8 block-scaled store + fp32 refine of the top-k' "
             "survivors; single [Q,P*k] all_gather merge")


def get_arch() -> ArchDef:
    return ArchDef(
        name="irli-deep1b", family="irli",
        abstract_params=_abstract_params, rules=_rules,
        cells={"train_scorers": _train_cell(), "serve_query": _serve_cell()},
        opt="adamw_nomaster",
        notes="the paper's own 100M-point distributed config (§5.3)")
