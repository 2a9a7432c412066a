"""Roofline analysis (deliverable g) — three terms per (arch x shape) cell
from the dry-run artifacts in benchmarks/results/dryrun/<mesh>/.

    compute term    = HLO_FLOPs_per_dev / peak_FLOP/s
    memory term     = HLO_bytes_per_dev / HBM_bw
    collective term = collective_bytes_per_dev / ICI_bw

with the peaks of the production target chip from repro.launch.mesh.PEAKS
(keyed by device kind, with their published source).

HLO_FLOPs / bytes / collective bytes come from benchmarks/hlo_analysis.py
(per-partition program, loop trip counts applied). MODEL_FLOPS is the 6ND /
2ND analytic count; the ratio MODEL/HLO catches remat + routing + padding
waste.
"""
from __future__ import annotations

import json
import pathlib
from typing import Optional

from repro.launch.mesh import TARGET_KIND, chip_peaks

_PEAK = chip_peaks(TARGET_KIND)
PEAK_FLOPS_BF16 = _PEAK["flops_bf16"]
HBM_BW = _PEAK["hbm_bw"]
ICI_BW = _PEAK["ici_bw"]


def analytic_model_flops(arch: str, shape_name: str) -> Optional[float]:
    """6*N*D (train) / 2*N*D (inference) with N = active params; per SYSTEM
    (all chips), not per device."""
    from repro.configs import get_arch, get_shape

    cfg, _ = get_arch(arch)
    shape = get_shape(arch, shape_name)
    fam = getattr(cfg, "family", None)
    if fam == "lm":
        n = cfg.n_active_params
        if shape.kind == "train":
            return 6.0 * n * shape["global_batch"] * shape["seq_len"]
        if shape.kind == "prefill":
            return 2.0 * n * shape["global_batch"] * shape["seq_len"]
        return 2.0 * n * shape["global_batch"]          # decode: 1 token/seq
    if fam == "gnn":
        H, D = cfg.n_heads, cfg.d_hidden
        if shape.kind == "batched_graphs":
            E = shape["n_edges"] * shape["batch"]
            N = shape["n_nodes"] * shape["batch"]
        else:
            E, N = shape.get("n_edges", 0), shape.get("n_nodes", 0)
        F = shape.get("d_feat", 64)
        # layer1 transform + SDDMM/SpMM, x3 for train (fwd+bwd)
        fwd = 2 * N * F * H * D + 6 * E * H * D
        return 3.0 * fwd
    if fam == "recsys":
        B = shape.get("batch", 1)
        if shape.kind == "retrieval":
            return 2.0 * shape["n_candidates"] * cfg.embed_dim
        mult = 3.0 if shape.kind == "train" else 1.0
        if cfg.kind == "bert4rec":
            d, L_ = cfg.embed_dim, cfg.seq_len
            per_tok = cfg.n_blocks * (4 * d * d + 8 * d * d) + 4 * d * L_
            return mult * 2.0 * B * L_ * per_tok
        if cfg.kind == "dien":
            d_in, g = 2 * cfg.embed_dim, cfg.gru_dim
            gru = cfg.seq_len * 2 * 3 * g * (d_in + g) * 2   # two GRU passes
            mlp = sum(2 * a * b for a, b in zip(
                (cfg.embed_dim + d_in + g,) + tuple(cfg.mlp_dims),
                tuple(cfg.mlp_dims) + (1,)))
            return mult * B * (gru + mlp)
        if cfg.kind == "wide_deep":
            d0 = len(cfg.tables) * cfg.embed_dim
            mlp = sum(2 * a * b for a, b in zip((d0,) + tuple(cfg.mlp_dims),
                                                tuple(cfg.mlp_dims) + (1,)))
            return mult * B * mlp
        if cfg.kind == "dcn_v2":
            d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
            cross = cfg.n_cross_layers * 2 * d0 * d0
            mlp = sum(2 * a * b for a, b in zip((d0,) + tuple(cfg.mlp_dims),
                                                tuple(cfg.mlp_dims)))
            return mult * B * (cross + mlp + 2 * (cfg.mlp_dims[-1] + d0))
    return None         # crawl cell: data-plane, no useful-FLOP notion


def analytic_hbm_floor(arch: str, shape_name: str, chips: int,
                       microbatches: int = 1) -> Optional[float]:
    """Perfect-fusion HBM traffic floor per device per step (bytes).

    The XLA-boundary estimate (hbm_bytes_est) is an upper bound inflated by
    CPU fusion granularity (+ bf16->f32 legalization); this floor assumes the
    TPU fusion ideal: ~8 activation materializations per transformer layer
    pass, weights streamed once per pass per microbatch, flash attention
    (no score traffic), minimal stash. Truth lies between floor and estimate;
    bottleneck classification uses the floor (optimistic-memory basis).
    """
    from repro.configs import get_arch, get_shape

    cfg, _ = get_arch(arch)
    shape = get_shape(arch, shape_name)
    fam = getattr(cfg, "family", None)
    if fam == "lm":
        B = shape["global_batch"]
        S = shape["seq_len"]
        L, d = cfg.n_layers, cfg.d_model
        dp, tp = chips // 16, 16
        P = cfg.n_active_params * 2                        # bf16
        act = B * S * d * 2 / dp                           # one (B,S,d) bf16/dev
        if shape.kind == "train":
            passes = 3                                     # fwd, remat-fwd, bwd
            act_io = 8 * act * L * passes
            stash = 2 * L * act                            # write + read once
            weights = P / tp * passes * microbatches
            xent = 2 * 2 * B * S * (cfg.vocab_size / tp) * 4 / dp
            return act_io + stash + weights + xent
        if shape.kind == "prefill":
            act_io = 8 * act * L
            kv = 2 * L * B * S * cfg.n_kv_heads * cfg.head_dim * 2 / dp
            return act_io + P / tp + kv
        # decode: weights + KV stream once per token
        kv = 2 * L * B * S * cfg.n_kv_heads * cfg.head_dim * 2 / chips
        return P / chips + kv + 8 * B * 1 * d * 2 * L / chips
    if fam == "gnn":
        if shape.kind == "batched_graphs":
            E = shape["n_edges"] * shape["batch"]
            N = shape["n_nodes"] * shape["batch"]
        else:
            E, N = shape.get("n_edges", 0), shape.get("n_nodes", 0)
        F = shape.get("d_feat", 64)
        HD = cfg.n_heads * cfg.d_hidden
        per_pass = (N * F + 2 * E * 4 + 3 * E * HD + 2 * N * HD) * 4
        return 3.0 * per_pass / chips
    if fam == "recsys":
        B = shape.get("batch", 1)
        if shape.kind == "retrieval":
            return shape["n_candidates"] * cfg.embed_dim * 4 / chips
        mult = 3.0 if shape.kind == "train" else 1.0
        n_fields = max(len(cfg.tables), 1)
        embed = B * n_fields * cfg.embed_dim * 4
        if cfg.kind == "bert4rec":
            embed = B * cfg.seq_len * cfg.embed_dim * 4 * (4 * cfg.n_blocks)
        if cfg.kind == "dien":
            embed += B * cfg.seq_len * (2 * cfg.embed_dim + 2 * cfg.gru_dim) * 4 * 2
        d0 = sum(cfg.mlp_dims) or 1
        acts = B * d0 * 4 * 2
        params = sum(a * b for a, b in zip(
            (n_fields * cfg.embed_dim,) + tuple(cfg.mlp_dims),
            tuple(cfg.mlp_dims) + (1,))) * 4
        return mult * (embed + acts) / chips + params / chips
    return None


def load_cell(results_dir: str, mesh: str, arch: str, shape: str) -> Optional[dict]:
    p = pathlib.Path(results_dir) / mesh / f"{arch}__{shape}.json"
    if not p.exists():
        return None
    return json.loads(p.read_text())


def roofline_row(rec: dict) -> dict:
    chips = rec["n_devices"]
    flops_dev = rec["flops_counted"]
    hbm_dev = rec["hbm_bytes_est"]
    coll_dev = rec["collective_bytes"]
    t_c = flops_dev / PEAK_FLOPS_BF16
    t_m = hbm_dev / HBM_BW
    # with the Pallas flash kernel, score/prob blocks stay in VMEM
    t_m_flash = (hbm_dev - rec.get("attn_interior_bytes", 0.0)) / HBM_BW
    floor = analytic_hbm_floor(rec["arch"], rec["shape"], chips,
                               rec.get("meta", {}).get("microbatches") or 1)
    t_m_floor = (floor / HBM_BW) if floor else t_m_flash
    t_x = coll_dev / ICI_BW
    dom = max((t_c, "compute"), (t_m_floor, "memory"), (t_x, "collective"))
    model = analytic_model_flops(rec["arch"], rec["shape"])
    ratio = (model / (flops_dev * chips)) if (model and flops_dev) else None
    return dict(
        arch=rec["arch"], shape=rec["shape"], chips=chips,
        t_compute=t_c, t_memory=t_m, t_memory_flash=t_m_flash,
        t_memory_floor=t_m_floor, t_collective=t_x,
        bottleneck=dom[1], model_flops=model, useful_ratio=ratio,
        mem_per_dev=rec["memory"].get("total_per_device", 0),
        step_time_lower_bound=max(t_c, t_m_floor, t_x),
        roofline_fraction=(model / chips / PEAK_FLOPS_BF16 /
                           max(t_c, t_m_floor, t_x)) if model else None,
    )


def main(results_dir: str = "benchmarks/results/dryrun", mesh: str = "single"):
    from repro.configs import all_cells

    rows = []
    for arch, shape in all_cells() + [("webparf", "crawl_step")]:
        rec = load_cell(results_dir, mesh, arch, shape)
        if rec is None:
            continue
        rows.append(roofline_row(rec))
    if not rows:
        print("(no dry-run artifacts yet — run `python -m repro.launch.dryrun "
              "--all --mesh single` first)")
        return rows

    print(f"\n== Roofline, {mesh} pod ({rows[0]['chips']} chips, "
          f"{TARGET_KIND} published peaks) — times are per-step lower "
          f"bounds ==")
    hdr = (f"{'arch':22s} {'shape':14s} {'compute':>8s} {'mem floor':>9s} "
           f"{'mem xla':>8s} {'collect':>8s} {'bound':>10s} "
           f"{'useful':>7s} {'roofline%':>9s}")
    print(hdr)
    for r in rows:
        uf = f"{r['useful_ratio']:.2f}" if r["useful_ratio"] else "  -"
        rf = f"{100*r['roofline_fraction']:.1f}" if r["roofline_fraction"] else "  -"
        print(f"{r['arch']:22s} {r['shape']:14s} {r['t_compute']:8.3f} "
              f"{r['t_memory_floor']:9.3f} {r['t_memory_flash']:8.3f} "
              f"{r['t_collective']:8.3f} {r['bottleneck']:>10s} "
              f"{uf:>7s} {rf:>9s}")
    return rows


if __name__ == "__main__":
    import sys
    main(mesh=sys.argv[1] if len(sys.argv) > 1 else "single")
