"""Small shared arithmetic for the metric readers."""

from __future__ import annotations

from typing import Any, Dict


def least_time(flops: float, nbytes: float, peaks: Dict[str, Any]) -> float:
    """The roofline's least time: the larger of the compute bound (at the
    bf16 peak) and the memory bound."""
    return max(flops / float(peaks["flops_per_s"]["bfloat16"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))
