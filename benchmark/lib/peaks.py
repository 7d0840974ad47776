"""Published peaks of each device, keyed by ``device_kind`` as JAX reports
it. An unknown kind is an error, not a default.

Not read by any metric yet: they are here for the kernels' roofline shares
(PERF.md, Open questions), which need the solver's executed rounds.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to lib/peaks.py") from None
