"""Roofline derivation from the dry-run artifacts (deliverable g).

Reads artifacts/dryrun/*.json (written by repro.launch.dryrun) and derives,
per (arch x shape x mesh):

  compute term    = HLO_FLOPs_per_chip / peak_FLOP/s           [s]
  memory term     = HLO_bytes_per_chip / HBM_bw                [s]
  collective term = collective_bytes_per_chip / link_bw        [s]

cost_analysis() on the SPMD-partitioned module reports *per-chip* flops and
bytes; the collective bytes come from summing operand sizes of every
collective in the per-chip optimized HLO (so they are also per-chip).  The
collective term conservatively assumes a single active ICI link direction.

MODEL_FLOPS uses 6*N*D (dense) / 6*N_active*D (MoE) with D = processed
tokens; the ratio MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat/redundancy
overhead (ratio < 1 when the compiled program does extra work, e.g. remat
recompute; > 1 would indicate the analytic count overstates e.g. for
encoder-only forward-only steps).
"""
from __future__ import annotations

import glob
import json
import os

from repro.launch.mesh import device_peaks
from repro.models.common import INPUT_SHAPES

# the chip these analytic tables are derived for (a jax ``device_kind``)
TARGET_KIND = "TPU v5 lite"

_BOTTLENECK_ADVICE = {
    "compute": "raise arithmetic efficiency: larger per-chip batch/seq tiles, "
               "fuse elementwise chains, or shrink redundant (remat) FLOPs",
    "memory": "cut HBM traffic: fuse producers into consumers, keep KV/latents "
              "in lower precision, widen blocks to raise arithmetic intensity",
    "collective": "reshard to cut collective volume: neighbor-permute consensus, "
                  "reduce-scatter instead of all-gather, overlap via async "
                  "collectives",
}


def tokens_processed(rec: dict) -> int:
    shape = INPUT_SHAPES[rec["shape"]]
    if rec["kind"] == "train":
        return shape.global_batch * shape.seq_len
    if rec["kind"] == "prefill":
        return shape.global_batch * shape.seq_len
    return shape.global_batch  # decode: one token per sequence


def derive(rec: dict) -> dict:
    peaks = device_peaks(TARGET_KIND)
    chips = rec["n_devices"]
    # prefer the loop-aware HLO accounting (cost_analysis counts lax.scan
    # bodies once -> ~n_layers too low; see repro.launch.hlo_analysis)
    tot = rec.get("hlo_totals", {}) or {}
    if "flops_dot" in tot:
        flops_chip = tot["flops_dot"]
        bytes_chip = tot["kernel_bytes"]
        coll_chip = tot["collective"]["total"]
    else:
        flops_chip = rec["cost_analysis"].get("flops", 0.0)
        bytes_chip = rec["cost_analysis"].get("bytes accessed", 0.0)
        coll_chip = rec["collective_bytes"].get("total", 0.0)

    compute_t = flops_chip / peaks["flops_bf16"]
    memory_t = bytes_chip / peaks["hbm_bw"]
    coll_t = coll_chip / peaks["ici_bw"]
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)

    n = rec["n_active_params"]
    d_tok = tokens_processed(rec)
    factor = 6 if rec["kind"] == "train" else 2
    model_flops = factor * n * d_tok
    hlo_total = flops_chip * chips
    ratio = model_flops / hlo_total if hlo_total else float("nan")

    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "kind", "n_devices")},
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_ratio": ratio,
        "advice": _BOTTLENECK_ADVICE[dominant],
        "collective_breakdown": {k: v for k, v in rec["collective_bytes"].items()
                                 if isinstance(v, float) and v > 0},
    }


def gather_mix_rows(ms=(1024, 4096, 16384, 131072), d_max: int = 12,
                    n: int = 1 << 20) -> list[dict]:
    """Analytic TPU roofline for the consensus step at fleet scale: dense
    (m, m) @ (m, n) vs the ELL gather-mix (``mix_sparse`` /
    ``mix_sparse_pallas``).  Needs no dry-run artifact -- the terms follow
    directly from the access pattern.

    dense:  reads P (m^2) + w (m n), writes (m n); 2 m^2 n flops.
    sparse: reads (d+1) rows of n per device + ELL tables (2 m d),
            writes (m n); 2 m (d+1) n flops.

    Dense flops cross sparse at m ~ d+1; dense *bytes* cross once
    m^2 > d m n, i.e. m > d n -- so on HBM-bound shapes the einsum stays
    competitive far longer than the flop count suggests, which is why the
    measured crossover (benchmarks/kernel_bench.py) sits orders of
    magnitude below the analytic memory crossover and the fleet engine
    switches on measured throughput, not this table."""
    peak_flops, hbm_bw = (device_peaks(TARGET_KIND)[k]
                          for k in ("flops_bf16", "hbm_bw"))
    out = []
    for m in ms:
        dense_flops = 2.0 * m * m * n
        dense_bytes = (m * m + 2.0 * m * n) * 4
        sparse_flops = 2.0 * m * (d_max + 1) * n
        sparse_bytes = ((d_max + 2.0) * m * n + 2.0 * m * d_max) * 4
        dense_t = max(dense_flops / peak_flops, dense_bytes / hbm_bw)
        sparse_t = max(sparse_flops / peak_flops, sparse_bytes / hbm_bw)
        out.append({
            "m": m, "d_max": d_max, "n": n,
            "dense_s": dense_t, "sparse_s": sparse_t,
            "dense_bound": ("compute" if dense_flops / peak_flops
                            >= dense_bytes / hbm_bw else "memory"),
            "winner": "sparse" if sparse_t < dense_t else "dense",
        })
    return out


def gather_mix_markdown(rows: list[dict]) -> str:
    lines = ["| m | d_max | n | dense s | sparse s | dense bound | winner |",
             "|---|---|---|---|---|---|---|"]
    for r in rows:
        lines.append(
            f"| {r['m']} | {r['d_max']} | {r['n']} | {r['dense_s']:.3e} "
            f"| {r['sparse_s']:.3e} | {r['dense_bound']} | {r['winner']} |")
    return "\n".join(lines)


def gather_mix_all() -> list[str]:
    from benchmarks.common import csv_line

    out = []
    for r in gather_mix_rows():
        out.append(csv_line(
            f"roofline_gather_mix[m={r['m']},d={r['d_max']}]",
            r["sparse_s"] * 1e6,
            f"dense_s={r['dense_s']:.3e};bound={r['dense_bound']};"
            f"winner={r['winner']}"))
    return out


def load_all(art_dir: str = "artifacts/dryrun") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            out.append(derive(json.load(f)))
    return out


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | useful FLOP ratio |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['compute_s']:.3e} "
            f"| {r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} "
            f"| {r['useful_ratio']:.2f} |")
    return "\n".join(lines)


def run_all(art_dir: str = "artifacts/dryrun") -> list[str]:
    from benchmarks.common import csv_line

    rows = load_all(art_dir)
    out = []
    for r in rows:
        dom_val = {"compute": r["compute_s"], "memory": r["memory_s"],
                   "collective": r["collective_s"]}[r["dominant"]]
        out.append(csv_line(
            f"roofline[{r['arch']}|{r['shape']}|{r['mesh']}]",
            dom_val * 1e6,
            f"dominant={r['dominant']};ratio={r['useful_ratio']:.2f}"))
    if rows:
        path = os.path.join(art_dir, "..", "roofline.md")
        with open(path, "w") as f:
            f.write(markdown_table(rows) + "\n")
    return out


if __name__ == "__main__":
    rows = load_all()
    print(markdown_table(rows))
