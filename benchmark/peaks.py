"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).  Copied from
``distributed_llm_scheduler_tpu/eval/benchlib.DEVICE_PEAKS`` so that the
program cannot move the yardstick.  A kind that is not here is an error,
never a default.
"""

from __future__ import annotations

from typing import Dict

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known kinds: {sorted(DEVICE_PEAKS)}"
        ) from None
