"""Published per-chip peaks, keyed by the device kind JAX reports
(``jax.devices()[0].device_kind``). A device that is not here has no
roofline: asking for one raises."""
from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # dense bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip"),
}


def peaks(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
