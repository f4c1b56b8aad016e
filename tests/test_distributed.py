"""Distributed IRLI (shard_map) correctness: run in a SUBPROCESS with 8 fake
host devices, compare the production sharded search against the single-shard
reference on identical data."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import make_production_search, shard_search_local
    from repro.core.network import ScorerConfig, scorer_init
    from repro.core.partition import hash_init, build_inverted_index
    from repro.core.search_api import SearchParams

    P_SHARDS = 8
    L_LOC, D, B, R = 512, 16, 32, 4
    rng = np.random.default_rng(0)

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    scorer = scorer_init(jax.random.PRNGKey(0),
                         ScorerConfig(d_in=D, d_hidden=32, n_buckets=B, n_reps=R))

    base = jnp.asarray(rng.normal(size=(P_SHARDS, L_LOC, D)), jnp.float32)
    members = []
    for s in range(P_SHARDS):
        a = hash_init(L_LOC, B, R, seed=s)
        members.append(build_inverted_index(a, B, max_load=2 * L_LOC // B).members)
    members = jnp.stack(members)

    queries = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)

    sp = SearchParams(m=4, tau=1, k=5, topC=1024)
    search = make_production_search(mesh, sp)
    res = search(scorer, members, base, queries)
    ids, scores = res.ids, res.scores

    # reference: loop shards on one device, merge manually
    ref_ids, ref_scores, ref_ncand = [], [], 0
    for s in range(P_SHARDS):
        r = shard_search_local(scorer, members[s], base[s], queries,
                               sp, q_chunk=16)
        ref_ids.append(np.where(np.asarray(r.ids) >= 0,
                                np.asarray(r.ids) + s * L_LOC, -1))
        ref_scores.append(np.asarray(r.scores))
        ref_ncand = ref_ncand + np.asarray(r.n_candidates)
    all_sc = np.concatenate(ref_scores, 1)
    all_id = np.concatenate(ref_ids, 1)
    order = np.argsort(-all_sc, 1)[:, :5]
    want_sc = np.take_along_axis(all_sc, order, 1)
    want_id = np.take_along_axis(all_id, order, 1)

    got_sc = np.asarray(scores)
    ok_scores = np.allclose(np.sort(got_sc, 1), np.sort(want_sc, 1),
                            rtol=1e-4, atol=1e-4)
    # id sets should match where scores are finite
    ok_ids = all(set(g[np.isfinite(s)]) == set(w[np.isfinite(ws)])
                 for g, s, w, ws in zip(np.asarray(ids), got_sc, want_id, want_sc))
    # SearchResult.n_candidates must be the psum of per-shard survivor counts
    ok_ncand = bool(np.array_equal(np.asarray(res.n_candidates), ref_ncand))

    # ---- make_distributed_search: per-shard DISTINCT scorers over "data" --
    from repro.core.distributed import local_search, make_distributed_search
    P2 = 4                      # the mesh's "data" axis
    scorers = [scorer_init(jax.random.PRNGKey(100 + s),
                           ScorerConfig(d_in=D, d_hidden=32, n_buckets=B,
                                        n_reps=R)) for s in range(P2)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *scorers)
    base2 = jnp.asarray(rng.normal(size=(P2, L_LOC, D)), jnp.float32)
    members2 = jnp.stack([
        build_inverted_index(hash_init(L_LOC, B, R, seed=20 + s), B,
                             max_load=2 * L_LOC // B).members
        for s in range(P2)])
    dsearch = make_distributed_search(mesh, sp)
    dres = dsearch(stacked, members2, base2, queries)
    # reference: per-shard local_search with each shard's own scorer
    ds, di, dn = [], [], 0
    for s in range(P2):
        r = local_search(scorers[s], members2[s], base2[s], queries, sp)
        di.append(np.where(np.asarray(r.ids) >= 0,
                           np.asarray(r.ids) + s * L_LOC, -1))
        ds.append(np.asarray(r.scores))
        dn = dn + np.asarray(r.n_candidates)
    dsc = np.concatenate(ds, 1)
    did = np.concatenate(di, 1)
    dorder = np.argsort(-dsc, 1)[:, :5]
    dwant_sc = np.take_along_axis(dsc, dorder, 1)
    dwant_id = np.take_along_axis(did, dorder, 1)
    dgot_sc = np.asarray(dres.scores)
    ok_dist_scores = np.allclose(np.sort(dgot_sc, 1), np.sort(dwant_sc, 1),
                                 rtol=1e-4, atol=1e-4)
    ok_dist_ids = all(
        set(g[np.isfinite(gs)]) == set(w[np.isfinite(ws)])
        for g, gs, w, ws in zip(np.asarray(dres.ids), dgot_sc,
                                dwant_id, dwant_sc))
    ok_dist_ncand = bool(np.array_equal(np.asarray(dres.n_candidates), dn))

    print(json.dumps({"ok_scores": bool(ok_scores), "ok_ids": bool(ok_ids),
                      "ok_ncand": ok_ncand,
                      "ok_dist_scores": bool(ok_dist_scores),
                      "ok_dist_ids": bool(ok_dist_ids),
                      "ok_dist_ncand": ok_dist_ncand,
                      "n_devices": len(jax.devices())}))
""")


def test_production_search_matches_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 8
    assert rec["ok_scores"], rec
    assert rec["ok_ids"], rec
    assert rec["ok_ncand"], rec
    assert rec["ok_dist_scores"], rec
    assert rec["ok_dist_ids"], rec
    assert rec["ok_dist_ncand"], rec


# ---------------------------------------------------------------------------
# The production search as one compiled program, on four fake devices: one
# subprocess (the device count is fixed when jax starts) whose readings the
# tests below assert on.
_FOUR = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import jax.monitoring as mon
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.analysis import audit, load_all
    from repro.core.distributed import (make_production_search, place_shards,
                                        shard_search_local)
    from repro.core.network import ScorerConfig, scorer_init
    from repro.core.partition import build_inverted_index, hash_init
    from repro.core.search_api import SearchParams
    from repro.launch.mesh import make_host_mesh
    from repro.store import encode

    counts = {"traces": 0, "compiles": 0}

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            counts["traces"] += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    mon.register_event_duration_secs_listener(on_duration)

    P_N, L, D, B, R, Q, K = 4, 256, 32, 16, 4, 16, 5
    rng = np.random.default_rng(0)
    scorer = scorer_init(jax.random.PRNGKey(0), ScorerConfig(
        d_in=D, d_hidden=32, n_buckets=B, n_reps=R))
    bases = [rng.normal(size=(L, D)).astype(np.float32) for _ in range(P_N)]
    queries = rng.normal(size=(Q, D)).astype(np.float32)
    # a cross-shard tie: shard 2 holds a copy of shard 0's row 7, and query
    # 0 is that row, so both copies score the same
    bases[2][40] = bases[0][7]
    queries[0] = bases[0][7]
    stores = [encode(b, "int8", 16) for b in bases]
    members = [build_inverted_index(hash_init(L, B, R, seed=s), B,
                                    max_load=2 * L // B).members
               for s in range(P_N)]
    # every bucket probed and every candidate kept: the exact top-k
    sp = SearchParams(m=B, tau=1, k=K, topC=L, store_dtype="int8")
    out = {}

    def run(mesh):
        sc, mem, st = place_shards(mesh, scorer, members, stores)
        q = jax.device_put(queries, NamedSharding(mesh, PartitionSpec()))
        search = make_production_search(mesh, sp)
        res = search(sc, mem, st, q)
        jax.block_until_ready(res.ids)
        before = dict(counts)
        for _ in range(5):
            again = search(sc, mem, st, q)
            jax.block_until_ready(again.ids)
        after = {k: counts[k] - before[k] for k in counts}
        return res, after, (sc, mem, st, q), search

    auto = make_host_mesh(P_N)
    res, after, args, search = run(auto)
    out["repeat"] = after
    sc, mem, st, q = args
    flat = list(auto.devices.flat)
    out["placed"] = {
        name: sorted([sh.index[0].start, flat.index(sh.device)]
                     for sh in arr.addressable_shards)
        for name, arr in (("members", mem), ("codes", st.codes),
                          ("scales", st.scales))}

    explicit = jax.make_mesh((P_N,), ("data",))
    eres, eafter, _, _ = run(explicit)
    out["explicit_axes"] = [str(t) for t in explicit.axis_types]
    out["explicit_equal"] = bool(
        np.array_equal(np.asarray(eres.ids), np.asarray(res.ids))
        and np.array_equal(np.asarray(eres.scores), np.asarray(res.scores)))
    out["explicit_repeat"] = eafter

    # the host merge of four single-device shard searches: shard order,
    # then rank within a shard, so a tie goes to the lower global id
    ids, scores, ncand = [], [], 0
    for s in range(P_N):
        r = shard_search_local(scorer, members[s], stores[s],
                               jnp.asarray(queries), sp)
        i = np.asarray(r.ids)
        ids.append(np.where(i >= 0, i + s * L, -1))
        scores.append(np.asarray(r.scores))
        ncand = ncand + np.asarray(r.n_candidates)
    ids, scores = np.concatenate(ids, 1), np.concatenate(scores, 1)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :K]
    out["host_ids"] = np.take_along_axis(ids, order, 1).tolist()
    out["host_scores"] = np.take_along_axis(scores, order, 1).tolist()
    out["host_ncand"] = ncand.tolist()
    out["ids"] = np.asarray(res.ids).tolist()
    out["scores"] = np.asarray(res.scores).tolist()
    out["ncand"] = np.asarray(res.n_candidates).tolist()
    out["tie"] = [7, 2 * L + 40]

    def arrays(*a):
        r = search(*a)
        return r.ids, r.scores, r.n_candidates

    hlo = jax.jit(arrays).lower(sc, mem, st, q).compile().as_text()
    out["merge_collectives"] = sorted(
        line.split("=", 2)[1].split("(")[0].strip().split(" ")[-1]
        for line in hlo.splitlines()
        if ("all-gather" in line or "all-reduce" in line)
        and "/merge/" in line)
    load_all()
    out["contract"] = audit("distributed.production_merge_collectives").passed
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _FOUR], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_production_search_compiles_once(four):
    """After the first call, repeated calls at one shape trace and compile
    nothing."""
    assert four["repeat"] == {"traces": 0, "compiles": 0}
    assert four["explicit_repeat"] == {"traces": 0, "compiles": 0}


def test_production_search_on_auto_and_explicit_meshes(four):
    assert four["explicit_axes"] == ["AxisType.Explicit"]
    assert four["explicit_equal"]


def test_place_shards_keeps_each_shard_on_its_device(four):
    for name in ("members", "codes", "scales"):
        assert four["placed"][name] == [[s, s] for s in range(4)], name


def test_production_search_equals_host_merge(four):
    assert four["ids"] == four["host_ids"]
    np.testing.assert_allclose(four["scores"], four["host_scores"],
                               rtol=1e-6)
    assert four["ncand"] == four["host_ncand"]
    # the tied copies both make the top-k, the lower global id first
    assert four["ids"][0][:2] == four["tie"]
    assert four["scores"][0][0] == four["scores"][0][1]


def test_merge_scope_in_the_compiled_program(four):
    """The all-gathers and the all-reduce of the merge carry its scope, and
    the registered collective contract holds on four devices."""
    # the scores' and the ids' all-gathers, and the counts' psum
    assert four["merge_collectives"] == ["all-gather", "all-gather",
                                         "all-reduce"]
    assert four["contract"]


def test_place_shards_on_one_device():
    """A one-device mesh takes one shard whole: ``place_shards`` and the
    production search serve it as the single-shard search does."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import (make_production_search, place_shards,
                                        shard_search_local)
    from repro.core.network import ScorerConfig, scorer_init
    from repro.core.partition import build_inverted_index, hash_init
    from repro.core.search_api import SearchParams
    from repro.launch.mesh import make_host_mesh
    from repro.store import encode

    L, D, B, R = 256, 16, 16, 4
    rng = np.random.default_rng(3)
    scorer = scorer_init(jax.random.PRNGKey(3), ScorerConfig(
        d_in=D, d_hidden=32, n_buckets=B, n_reps=R))
    store = encode(rng.normal(size=(L, D)).astype(np.float32), "int8", 16)
    members = build_inverted_index(hash_init(L, B, R, seed=3), B,
                                   max_load=2 * L // B).members
    queries = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)
    sp = SearchParams(m=4, tau=1, k=5, store_dtype="int8")
    mesh = make_host_mesh(1)
    sc, mem, st = place_shards(mesh, scorer, [members], [store])
    assert mem.shape == (1,) + members.shape
    assert st.codes.shape == (1,) + store.codes.shape
    got = make_production_search(mesh, sp)(sc, mem, st, queries)
    want = shard_search_local(scorer, members, store, queries, sp)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(want.scores), rtol=1e-6)
