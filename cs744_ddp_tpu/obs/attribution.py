"""Join analytic :class:`~cs744_ddp_tpu.analysis.costmodel.CostReport`\\ s
with measured wall-clock (ISSUE 8 tentpole b).

The cost model says what a program MUST do (flops, HBM bytes, wire
bytes); a measured per-dispatch time says what it DID.  The join yields:

- **MFU** — achieved flops/s over the bf16 peak of the device that was
  measured (per chip: shard_map reports are per-device, so ``flops /
  measured_s`` is already per-chip); absent for a device outside the
  peak table.
- **Roofline side** — whether the analytic compute time or the analytic
  HBM time dominates, plus the utilization ceiling that side imposes.
- **Comm/compute ratio** — serial wire seconds per compute second, the
  static version of the paper's sync-cost spectrum.
- **Exposed-comm bound** — for the ``overlap`` strategy: with a chain
  depth of 1, at most the LARGEST collective is exposed; ``ddp``'s
  barrier-chained plan pays the full sum (round-7 ladder, measured here
  against the same ICI model).
- **HBM residency** (round 20) — when the caller supplies the program's
  static liveness certificate (:func:`analysis.memlife.mem_report`),
  the record also carries the certified peak and its headroom against
  the chip capacity, so one attribution row answers both "how fast" and
  "does it fit".
"""

from __future__ import annotations

from typing import Dict, Optional

from ..analysis.costmodel import (CostReport,
                                  PEAK_BF16_FLOPS_BY_DEVICE_KIND,
                                  V5E_BF16_PEAK_FLOPS,
                                  V5E_HBM_BYTES_PER_S,
                                  V5E_HBM_CAPACITY_BYTES,
                                  V5E_ICI_BYTES_PER_S, mfu_fields)

__all__ = ["attribute", "overlap_vs_ddp", "mfu_fields"]


def attribute(report: CostReport, *, measured_s: Optional[float] = None,
              device_kind: Optional[str] = None, mem_report=None,
              peak_flops: float = V5E_BF16_PEAK_FLOPS,
              hbm_bytes_per_s: float = V5E_HBM_BYTES_PER_S,
              hbm_capacity_bytes: int = V5E_HBM_CAPACITY_BYTES,
              ici_bytes_per_s: float = V5E_ICI_BYTES_PER_S) -> Dict:
    """Attribution record for one program: the analytic fields are about
    the named v5e target; ``measured_s`` (per-dispatch seconds, same
    per-device scope as the report) adds the measured-join fields, with
    the MFU only when ``device_kind`` — the device that was timed — is in
    the peak table.  ``mem_report`` (an :class:`analysis.memlife.MemReport`
    for the SAME program) adds the certified peak-residency fields."""
    compute_s = report.flops / peak_flops
    hbm_s = report.hbm_bytes / hbm_bytes_per_s
    comm_s = report.wire_bytes / ici_bytes_per_s
    denom = max(compute_s, hbm_s)
    out = {
        "program": report.name,
        "gflops": round(report.flops / 1e9, 4),
        "hbm_mib": round(report.hbm_bytes / 2**20, 3),
        "wire_mib": round(report.wire_bytes / 2**20, 4),
        "analytic_compute_s": compute_s,
        "analytic_hbm_s": hbm_s,
        "analytic_comm_s": comm_s,
        "roofline_bound": "compute" if compute_s >= hbm_s else "bandwidth",
        # The MFU ceiling the dominant roofline side permits: 1.0 when
        # compute-bound, compute_s/hbm_s when the HBM wall caps it.
        "mfu_roofline_ceiling": round(compute_s / denom, 4) if denom else None,
        "comm_compute_ratio": (round(comm_s / compute_s, 4)
                               if compute_s else None),
        "arithmetic_intensity": (round(report.arithmetic_intensity, 2)
                                 if report.hbm_bytes else None),
    }
    if measured_s:
        achieved = report.flops / measured_s
        out["measured_s"] = round(measured_s, 6)
        out["achieved_tflops_per_sec"] = round(achieved / 1e12, 4)
        measured_peak = PEAK_BF16_FLOPS_BY_DEVICE_KIND.get(device_kind)
        if measured_peak is not None:
            out["mfu_vs_bf16_peak"] = round(achieved / measured_peak, 6)
    if mem_report is not None:
        peak = int(mem_report.peak_bytes)
        out["peak_hbm_mib"] = round(peak / 2**20, 3)
        out["hbm_headroom_mib"] = round(
            (hbm_capacity_bytes - peak) / 2**20, 3)
        out["hbm_capacity_utilization"] = round(
            peak / hbm_capacity_bytes, 6) if hbm_capacity_bytes else None
    return out


def overlap_vs_ddp(overlap_report: CostReport, ddp_report: CostReport, *,
                   ici_bytes_per_s: float = V5E_ICI_BYTES_PER_S) -> Dict:
    """Exposed-comm upper bound of the un-chained ``overlap`` plan vs the
    serial cost of ``ddp``'s chained bucket plan (per scanned step: uses
    the static per-instruction collective sizes, not loop-weighted
    totals)."""
    exposed = (max(overlap_report.collective_sizes)
               if overlap_report.collective_sizes else 0)
    chained = sum(ddp_report.collective_sizes)
    exposed_s = exposed / ici_bytes_per_s
    chained_s = chained / ici_bytes_per_s
    return {
        "overlap_exposed_bytes_upper_bound": exposed,
        "ddp_chained_bytes": chained,
        "overlap_exposed_comm_s_upper_bound": exposed_s,
        "ddp_chained_comm_s": chained_s,
        "hiding_ratio_lower_bound": (round(chained_s / exposed_s, 2)
                                     if exposed_s else None),
    }
