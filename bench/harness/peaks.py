"""Published peaks of one chip, keyed by ``device_kind``.

One TPU v5e chip reports the kind "TPU v5 lite".  Source: Google Cloud
documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s
bf16, 16 GB HBM at 819 GB/s.  No fp32 vector-unit peak
is published, so a compute bound taken from the bf16 peak under-reads
VPU-bound fp32 work.  A device that is not here is an error."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to bench/harness/peaks.py") from None


def roofline_share(flops: float, nbytes: float, seconds: float, kind: str):
    """(share in %, bound) of the least time the chip could take, the larger
    of flops/peak and bytes/peak, over the measured ``seconds``."""
    pk = peaks(kind)
    t_c, t_m = flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
