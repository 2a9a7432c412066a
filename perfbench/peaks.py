"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect. A kind that is not here is an error, not a
default.
"""
PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bytes=16e9, hbm_bw=819e9,
                        ici_bw=1600e9 / 8),
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
