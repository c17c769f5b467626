"""Hardware peaks, keyed by ``jax.devices()[0].device_kind``.

The benchmark's own table (the program has one too, for its profiler; a
later PR cannot move this one). A kind that is not here is an error,
never a default. Source: Google Cloud documentation, "TPU v5e": 197
TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks on record for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
