"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never
a default: a share of a peak that was guessed means nothing.

Copied from ``paddle_tpu/observability/flops.py`` (without its CPU
fallback) so that a later change to the program cannot move the
yardstick."""

from __future__ import annotations

#: device_kind substring -> peaks.  Source: Google Cloud documentation,
#: "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s chip-to-chip.
PEAKS = {
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
    "v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
            "hbm_bytes": 16e9,
            "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    kind = device_kind.lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise KeyError(
        f"device kind {device_kind!r} is not in benchmark/peaks.py — add "
        f"its published peaks with their source; no default is assumed")
