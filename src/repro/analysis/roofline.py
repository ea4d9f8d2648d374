"""Roofline terms from a compiled dry-run artifact.

compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
memory term     = HLO_bytes / (chips x HBM_bw)
collective term = collective_bytes / (chips x link_bw)

All tallies are per-device (the SPMD program IS the per-device program), so
dividing by per-chip peaks gives the same ratio as global/(chips x peak).

``compiled.cost_analysis()`` does NOT multiply while-loop (lax.scan) bodies
by their trip count, so it undercounts layer-stacked programs by ~L; we use
the call-graph parser in hlo_parse.py (trip counts from known_trip_count)
and report the XLA numbers alongside for reference.

Hardware constants are the TPU v5e row of ``core.capacity.PEAKS`` (per
chip: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI link).
"""
from __future__ import annotations

from repro.analysis.hlo_parse import parse_hlo
from repro.core.capacity import PEAKS, TPU_V5E

PEAK_FLOPS = PEAKS[TPU_V5E].flops     # bf16 per chip
HBM_BW = PEAKS[TPU_V5E].hbm_bw        # bytes/s per chip
ICI_BW = PEAKS[TPU_V5E].ici_bw        # bytes/s per link


def model_flops(arch, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); prefill 2*N*D; decode per token."""
    cfg = arch.model
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_active * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_active * toks
    return 2.0 * n_active * shape.global_batch   # decode: one token/sequence


def roofline_from_hlo_text(hlo_text: str, chips: int, cost: dict,
                           mf_total: float) -> dict:
    stats = parse_hlo(hlo_text)
    xla_flops = float(cost.get("flops", 0.0) or 0.0)
    xla_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    hlo_flops = max(stats["dot_flops"], xla_flops)
    hbm_bytes = max(stats["hbm_bytes"], xla_bytes)
    coll_bytes = stats["collective_bytes"]

    terms = {
        "compute_s": hlo_flops / PEAK_FLOPS,
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
    }
    bottleneck = max(terms, key=terms.get)
    mf_per_chip = mf_total / chips
    bound = max(terms.values())
    return {
        "chips": chips,
        "hlo_flops_per_chip": hlo_flops,
        "xla_cost_flops": xla_flops,
        "parsed_dot_flops": stats["dot_flops"],
        "hbm_bytes_per_chip": hbm_bytes,
        "xla_bytes_accessed": xla_bytes,
        "collective_bytes_per_chip": coll_bytes,
        "collective_counts": stats["collective_counts"],
        **terms,
        "bottleneck": bottleneck,
        "model_flops_total": mf_total,
        "useful_flops_ratio": (mf_per_chip / hlo_flops) if hlo_flops else None,
        "step_time_bound_s": bound,
        "mfu_bound": (mf_per_chip / PEAK_FLOPS) / bound if bound > 0 else None,
    }


def roofline_from_lowered(lowered, compiled, mesh, arch, shape) -> dict:
    return roofline_from_hlo_text(
        compiled.as_text(), mesh.size, compiled.cost_analysis(),
        model_flops(arch, shape))
