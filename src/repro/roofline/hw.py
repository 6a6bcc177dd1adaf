"""Target hardware constants (TPU v5e) for roofline terms and CommPolicy.

Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.  The roofline
terms use them as the target chip; they are not measurements.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str
    peak_bf16_flops: float      # FLOP/s per chip
    hbm_bw: float               # bytes/s per chip
    hbm_bytes: float            # capacity per chip
    ici_link_bw: float          # bytes/s per ICI link direction
    ici_links: int              # links per chip on the 2-D torus
    dcn_bw: float               # cross-pod bytes/s per chip
    vmem_bytes: float = 128 * 2 ** 20
    mxu_tile: int = 128


V5E = HwSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2 ** 30,
    ici_link_bw=50e9,
    ici_links=4,
    dcn_bw=6.25e9,   # ~50 Gb/s effective per-chip cross-pod budget
)
