"""Roofline analysis per (arch x shape x mesh) from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.roofline [--mesh single|multi] [--md]

Terms (seconds, PER DEVICE per step — the dry-run HLO is the SPMD per-device
program; trip-count-corrected by benchmarks/hlo_analysis.py):

    compute    = flops / PEAK_FLOPS        (197 TFLOP/s bf16, TPU v5e)
    memory     = hbm_bytes / HBM_BW        (819 GB/s)
    collective = collective_bytes / ICI_BW (50 GB/s/link; bytes already
                                            per-device result-bytes)

MODEL_FLOPS = minimal algorithmic flops (6·N·D for LM train, 2·N·D serve,
attention + family-specific formulas below), divided by device count —
the "useful fraction" MODEL_FLOPS / HLO_FLOPS exposes remat/dispatch waste.
"""
from __future__ import annotations

import argparse
import json
import os

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")

# ------------------------------------------------------- MODEL_FLOPS --------
LM = {
    "gemma-7b": dict(L=28, d=3072, H=16, Kv=16, Dh=256, ff=24576, V=256000,
                     active=None, window=None),
    "yi-6b": dict(L=32, d=4096, H=32, Kv=4, Dh=128, ff=11008, V=64000,
                  active=None, window=None),
    "qwen3-4b": dict(L=36, d=2560, H=32, Kv=8, Dh=128, ff=9728, V=151936,
                     active=None, window=None),
    "mixtral-8x7b": dict(L=32, d=4096, H=32, Kv=8, Dh=128, ff=14336, V=32000,
                         active=2, experts=8, window=4096),
    "llama4-maverick-400b-a17b": dict(L=48, d=5120, H=40, Kv=8, Dh=128,
                                      ff=8192, V=202048, active=2,  # top1+shared
                                      experts=128, window=8192),
}

SHAPES = {"train_4k": (4096, 256), "prefill_32k": (32768, 32),
          "decode_32k": (32768, 128), "long_500k": (524288, 1)}


def lm_params_active(c):
    attn = c["d"] * (c["H"] * c["Dh"] * 2 + c["Kv"] * c["Dh"] * 2)
    n_ff = c.get("active") or 1
    mlp = 3 * c["d"] * c["ff"] * n_ff
    per_layer = attn + mlp + 2 * c["d"]
    return c["L"] * per_layer + c["V"] * c["d"]


def lm_model_flops(arch, shape):
    c = LM[arch]
    S, B = SHAPES[shape]
    N = lm_params_active(c)
    ctx = min(S, c["window"]) if c["window"] else S
    if shape == "train_4k":
        T = S * B
        param_f = 6 * N * T
        attn_f = 3 * 2 * 2 * T * (S / 2) * c["H"] * c["Dh"]   # fwd+bwd(2x)
        return param_f + attn_f
    if shape == "prefill_32k":
        T = S * B
        return 2 * N * T + 2 * 2 * T * (ctx / 2) * c["H"] * c["Dh"]
    # decode: one token per sequence
    return 2 * N * B + 2 * 2 * B * ctx * c["H"] * c["Dh"]


def recsys_model_flops(arch, shape):
    mult = 3 if shape == "train_batch" else 1
    B = {"train_batch": 65536, "serve_p99": 512, "serve_bulk": 262144,
         "retrieval_cand": 1}[shape]
    if shape == "retrieval_cand":
        d = {"dlrm-mlperf": 128, "dien": 36, "bst": 32, "xdeepfm": 10}[arch]
        return 2 * 1_048_576 * d
    if arch == "dlrm-mlperf":
        bot = 2 * (13 * 512 + 512 * 256 + 256 * 128)
        inter = 2 * 27 * 27 * 128
        top = 2 * (479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
        return mult * B * (bot + inter + top)
    if arch == "dien":
        d2, g, T = 36, 108, 100
        gru = 2 * T * 3 * (d2 * g + g * g) * 2          # 2 GRU passes
        att = 2 * T * ((g + d2) * 80 + 80 * 40 + 40)
        top = 2 * ((g + 2 * d2) * 200 + 200 * 80 + 80)
        return mult * B * (gru + att + top)
    if arch == "bst":
        d, T = 32, 21
        attn = 2 * 2 * T * T * d + 2 * 4 * T * d * d
        ff = 2 * T * (d * 4 * d * 2)
        top = 2 * ((T * d + 8 * d) * 1024 + 1024 * 512 + 512 * 256 + 256)
        return mult * B * (attn + ff + top)
    if arch == "xdeepfm":
        m, D = 39, 10
        cin = 0
        hk = m
        for h in (200, 200, 200):
            cin += 2 * hk * m * D + 2 * hk * m * D * h
            hk = h
        deep = 2 * (m * D * 400 + 400 * 400 + 400)
        return mult * B * (cin + deep)
    raise KeyError(arch)


def gnn_model_flops(shape):
    d, rbf, n_int = 64, 300, 3
    cells = {"full_graph_sm": (3072, 10752, 1433, 16),
             "minibatch_lg": (169984, 168960, 602, 41),
             "ogb_products": (2449408, 61865984, 100, 47),
             "molecule": (4096, 8192, 0, 1)}
    N, E, d_in, n_out = cells[shape]
    per_int = 2 * E * (rbf * d + d * d) + E * d + 2 * N * d * d * 3
    embed = 2 * N * max(d_in, 1) * d
    head = 2 * N * (d * d // 2 + d // 2 * n_out)
    return 3 * (n_int * per_int + embed + head)   # train: fwd+bwd


def irli_model_flops(shape, n_dev=256):
    R, d, H, B_buckets = 32, 96, 1024, 20000
    if shape == "train_scorers":
        batch = 1 << 15
        return 3 * batch * R * 2 * (d * H + H * B_buckets)
    # serve: by DESIGN every corpus shard scores ALL queries against its own
    # slice (paper §5.3 — zero cross-node traffic until the merge), so the
    # scorer work is replicated x n_dev; multiply so the per-device division
    # in build() cancels for the replicated part.
    q, topc = 4096, 1024
    per_dev = q * (R * 2 * (d * H + H * B_buckets) + 2 * topc * d)
    return per_dev * n_dev


def model_flops(arch, shape, n_dev=256):
    # NOTE: useful_frac may exceed 1 for scatter/gather-dominated archs
    # (schnet): the analytic model bills per-edge elementwise message work
    # that lowers to non-dot HLO ops, which the HLO counter (dots/convs
    # only) does not see.
    if arch in LM:
        return lm_model_flops(arch, shape)
    if arch == "schnet":
        return gnn_model_flops(shape)
    if arch == "irli-deep1b":
        return irli_model_flops(shape, n_dev)
    return recsys_model_flops(arch, shape)


def kernel_roofline(name, seconds, flops, hbm_bytes):
    """Achieved-vs-peak for ONE measured kernel invocation — the live
    counterpart of build(): HLO-counted flops/bytes (hlo_analysis.analyze_hlo
    over the kernel's own compiled module) plus a wall-clock timing, instead
    of dry-run artifacts. Peaks are the TPU v5e chip numbers above, so on
    the CPU container ``frac_of_roofline`` is a cross-platform reference
    point rather than a local efficiency claim (benchmarks.
    bench_kernel_roofline records both and pushes them through the metrics
    registry)."""
    t_c = flops / PEAK_FLOPS
    t_m = hbm_bytes / HBM_BW
    return {
        "name": name, "seconds": seconds,
        "flops": flops, "hbm_bytes": hbm_bytes,
        "achieved_gflops": flops / seconds / 1e9 if seconds > 0 else 0.0,
        "achieved_gbps": hbm_bytes / seconds / 1e9 if seconds > 0 else 0.0,
        "peak_gbps": HBM_BW / 1e9,
        "bound": "compute" if t_c >= t_m else "memory",
        "frac_of_roofline": max(t_c, t_m) / seconds if seconds > 0 else 0.0,
    }


# ------------------------------------------------------------- the table ----
def build(mesh: str, use_corrected: bool = True):
    with open(os.path.join(ART, f"dryrun_{mesh}.json")) as f:
        d = json.load(f)
    n_dev = 512 if mesh == "multi" else 256
    rows = []
    for key, v in sorted(d.items()):
        arch, shape = key.split("/")
        if v["status"] == "skip":
            rows.append({"cell": key, "status": "skip", "why": v["reason"]})
            continue
        if v["status"] != "ok":
            rows.append({"cell": key, "status": "error"})
            continue
        c = v.get("corrected", {})
        flops = c.get("flops") or v.get("flops") or 0
        hbm = c.get("hbm_bytes") or v.get("bytes_accessed") or 0
        coll = c.get("collective_bytes", 0)
        t_c = flops / PEAK_FLOPS
        t_m = hbm / HBM_BW
        t_n = coll / ICI_BW
        dom = max((("compute", t_c), ("memory", t_m), ("collective", t_n)),
                  key=lambda x: x[1])[0]
        mf = model_flops(arch, shape, n_dev) / n_dev
        rows.append({
            "cell": key, "status": "ok",
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
            "dominant": dom,
            "model_flops_per_dev": mf,
            "hlo_flops_per_dev": flops,
            "useful_frac": mf / flops if flops else 0.0,
            "roofline_frac": (mf / PEAK_FLOPS) / max(t_c, t_m, t_n)
            if max(t_c, t_m, t_n) > 0 else 0.0,
            "temp_gib": v.get("temp_size_in_bytes", 0) / 2**30,
            "collectives": c.get("collectives", {}),
        })
    return rows


def markdown(rows, mesh):
    out = [f"### Roofline — {mesh} pod mesh "
           f"({'2x16x16=512' if mesh == 'multi' else '16x16=256'} chips, "
           "TPU v5e: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s ICI)", "",
           "| cell | compute s | memory s | collective s | bound | useful "
           "(model/HLO) | roofline frac | temp GiB |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] == "skip":
            out.append(f"| {r['cell']} | — | — | — | SKIP | — | — | — |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['cell']} | ERROR |  |  |  |  |  |  |")
            continue
        out.append(
            f"| {r['cell']} | {r['compute_s']:.3g} | {r['memory_s']:.3g} | "
            f"{r['collective_s']:.3g} | {r['dominant']} | "
            f"{r['useful_frac']:.2f} | {r['roofline_frac']:.2f} | "
            f"{r['temp_gib']:.1f} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--md", action="store_true")
    args = ap.parse_args()
    rows = build(args.mesh)
    with open(os.path.join(ART, f"roofline_{args.mesh}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    if args.md:
        print(markdown(rows, args.mesh))
    else:
        for r in rows:
            if r["status"] == "ok":
                print(f"{r['cell']:42s} {r['dominant']:10s} "
                      f"cmp={r['compute_s']:.3g} mem={r['memory_s']:.3g} "
                      f"net={r['collective_s']:.3g} "
                      f"useful={r['useful_frac']:.2f} "
                      f"roof={r['roofline_frac']:.2f}")
            else:
                print(f"{r['cell']:42s} {r['status'].upper()}")


if __name__ == "__main__":
    main()
