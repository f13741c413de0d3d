"""Hardware model for the target platform (TPU v5e-class chip).

These constants drive (a) the autotuner's predictive model (the paper's
Eq.2/Eq.3 cache bounds become VMEM bounds), (b) the scoped-VMEM limit
every Pallas kernel compiles with, and (c) the roofline terms in
benchmarks/roofline.py.  Peaks are Google Cloud's published TPU v5e
figures: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM; ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses

MiB = 1024 * 1024


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float        # per chip
    hbm_bw: float                 # bytes/s per chip
    ici_bw_per_link: float        # bytes/s per link
    ici_links: int                # links per chip (2D torus)
    hbm_bytes: int                # capacity per chip
    vmem_bytes: int               # software-managed on-chip buffer
    mxu_dim: int = 128            # systolic array edge
    sublane: dict = dataclasses.field(
        default_factory=lambda: {"float32": 8, "bfloat16": 16, "float64": 4}
    )
    # Calibrated roofline coefficients (DESIGN.md §9).  The nominal spec
    # above is the datasheet; these scale it to what the measurement cache
    # actually observed: effective bandwidth = hbm_bw * hbm_efficiency,
    # effective compute = peak_flops * mxu_efficiency, plus a fitted
    # per-grid-step overhead.  ``core/evaluator.fit_hw`` fills them via
    # least squares; ``calibrated`` marks a fitted spec (the predictive
    # model switches from the max-roofline to the fitted additive form).
    mxu_efficiency: float = 1.0
    hbm_efficiency: float = 1.0
    grid_overhead_s: float = 1.5e-7
    calibrated: bool = False

    @property
    def peak_flops_f32(self) -> float:
        return self.peak_flops_bf16 / 4  # MXU f32 via passes

    def peak_flops(self, dtype: str) -> float:
        return self.peak_flops_bf16 if dtype == "bfloat16" else self.peak_flops_f32

    @property
    def ridge_flops_per_byte(self) -> float:
        return self.peak_flops_bf16 / self.hbm_bw


TPU_V5E = HwSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024 * MiB,
    vmem_bytes=128 * MiB,         # physical VMEM per TensorCore
)

# The ONE VMEM budget: the feasibility gate (``vmem_model.feasible``)
# admits a plan only if its modelled working set fits it, and every
# ``pallas_call`` passes it to Mosaic as ``vmem_limit_bytes`` (without
# it Mosaic applies a 16 MiB default and refuses admitted plans).  Kept
# well below the physical VMEM: the model's estimate and the compiler's
# allocation differ by a few MiB in either direction, and the margin
# covers compiler scratch and semaphores.
VMEM_LIMIT_BYTES = 48 * MiB

# ``jax.devices()[i].device_kind`` -> spec.  A TPU whose kind is not here
# is an error (see ``spec_for_device_kind``), never a silent default.
SPECS_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def spec_for_device_kind(kind: str) -> HwSpec:
    try:
        return SPECS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise ValueError(
            f"no hardware spec for device kind {kind!r}; known: "
            f"{sorted(SPECS_BY_DEVICE_KIND)} (add it to core/hw.py)") from None

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8, "int8": 1}


def dtype_bytes(dtype) -> int:
    return DTYPE_BYTES[str(dtype)]
