"""Hardware platform database.

Encodes every platform the paper characterizes (Table 2 / Table 3):

* ``rm_pim``    — PIM-enabled Racetrack (domain-wall) memory, PIRM [13] / FPIRM [19]
* ``ddr3_pim``  — DDR3-1600 PIM (ELP^2IM [20]), 16 dies per tested 1 GB DIMM
* ``gpu``       — NVIDIA Jetson Xavier NX mobile GPU
* ``fpga``      — AMD/Xilinx Versal Prime VM1802

plus the beyond-paper TPU v5e target used for the multi-pod roofline and the
fleet-level sustainability analysis.

Power-state values for the paper platforms: *active* powers are the paper's
measured Table-3 workload powers; *idle*/*sleep* powers are not published in
the paper (it relies on GreenChip defaults) and are calibrated here so that
every Figure-2 claim reproduces (see DESIGN.md §10 and
tests/test_sustain.py::test_paper_claims_*).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class PowerStates:
    """Power draw (watts) in the three GreenChip duty states."""

    active_w: float
    idle_w: float
    sleep_w: float

    def validate(self) -> None:
        if not (self.active_w >= self.idle_w >= self.sleep_w >= 0.0):
            raise ValueError(f"power states must be ordered: {self}")


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """A platform whose embodied + operational sustainability we evaluate."""

    name: str
    die_area_mm2: float
    tech_node_nm: float
    lca_study: str                      # key into lca.STUDIES
    power: PowerStates
    # Compute/memory roofline constants (None where not meaningful, e.g. DIMMs)
    peak_flops: Optional[float] = None  # FLOP/s at the native compute dtype
    hbm_bw: Optional[float] = None      # bytes/s
    link_bw: Optional[float] = None     # bytes/s per ICI/interconnect link
    mem_bytes: Optional[float] = None
    dies_per_module: int = 1            # e.g. 16 DRAM dies / 1 GB DIMM (Table 2 fn.5)
    # Paper-published dies/wafer (Table 2); geometric model used when absent.
    dies_per_wafer_published: Optional[int] = None
    notes: str = ""

    def __post_init__(self):
        self.power.validate()


# ----------------------------------------------------------------------------
# Paper platforms (Table 2 rows; active powers from Table 3)
# ----------------------------------------------------------------------------

# The paper evaluates the RM die under three LCA studies (Boyd'11, Higgs'09,
# imec PPACE'20). ``rm_pim`` pins the headline Boyd'11 estimate; the
# per-study variants are produced by core.lca (see embodied_energy_mj).
RM_PIM = DeviceSpec(
    name="rm_pim",
    die_area_mm2=38.0,
    tech_node_nm=32.0,
    lca_study="boyd2011",
    power=PowerStates(active_w=0.93, idle_w=0.025, sleep_w=0.002),
    dies_per_module=16,   # like-for-like 1 GB PIM DIMM replacement (vs DDR3)
    dies_per_wafer_published=1847,
    notes="PIRM/FPIRM PIM-enabled domain-wall memory; +3 spintronic masks [14]",
)

DDR3_PIM = DeviceSpec(
    name="ddr3_pim",
    die_area_mm2=73.0,
    tech_node_nm=55.0,
    lca_study="boyd2011_dram",
    power=PowerStates(active_w=2.0, idle_w=0.5, sleep_w=0.1),
    dies_per_module=16,   # Table 2 footnote 5: 16 dies per tested 1 GB DIMM
    dies_per_wafer_published=967,
    notes="DDR3-1600 PIM per ELP^2IM [20]",
)

JETSON_NX = DeviceSpec(
    name="gpu",
    die_area_mm2=350.0,
    tech_node_nm=14.0,
    lca_study="bardon2020",
    power=PowerStates(active_w=21.05, idle_w=2.0, sleep_w=0.3),
    peak_flops=21e12,     # fp16 dense (Xavier NX marketing 21 TOPS class)
    dies_per_wafer_published=201,
    notes="NVIDIA Jetson Xavier NX mobile GPU",
)

VERSAL_VM1802 = DeviceSpec(
    name="fpga",
    die_area_mm2=324.0,
    tech_node_nm=7.0,
    lca_study="bardon2020",
    power=PowerStates(active_w=7.74, idle_w=2.5, sleep_w=0.5),
    dies_per_wafer_published=217,
    notes="AMD/Xilinx Versal Prime VM1802",
)

# ----------------------------------------------------------------------------
# Beyond-paper target: TPU v5e (the platform of the multi-pod dry-run).
# Die area / node / power are public-information estimates, flagged as such.
# ----------------------------------------------------------------------------

TPU_V5E = DeviceSpec(
    name="tpu_v5e",
    die_area_mm2=325.0,                 # estimate (v4 ~ <400 mm^2; v5e smaller)
    tech_node_nm=5.0,
    lca_study="bardon2020",
    power=PowerStates(active_w=200.0, idle_w=60.0, sleep_w=10.0),
    peak_flops=197e12,                  # bf16, per chip (assignment constant)
    hbm_bw=819e9,                       # bytes/s HBM (assignment constant)
    link_bw=50e9,                       # bytes/s per ICI link (assignment constant)
    mem_bytes=16 * 1024**3,             # 16 GB HBM
    dies_per_module=1,
    notes="beyond-paper fleet target; embodied estimate = logic die via PPACE "
          "curve + 8 HBM DRAM-die equivalents (cross-study caveat applies)",
)

DEVICES: Dict[str, DeviceSpec] = {
    d.name: d for d in (RM_PIM, DDR3_PIM, JETSON_NX, VERSAL_VM1802, TPU_V5E)
}

# Accelerators as JAX reports them (``jax.devices()[0].device_kind``) ->
# the spec whose peaks and power the accountant bills a run against.
DEVICE_KINDS: Dict[str, DeviceSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def spec_for_kind(device_kind: str) -> DeviceSpec:
    """The DeviceSpec of an attached accelerator. A kind missing from
    :data:`DEVICE_KINDS` is an error, never a default: billing one chip at
    another's peaks and power would misstate every J/token."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"no DeviceSpec for device_kind {device_kind!r}; add it to "
            f"repro.core.hw.DEVICE_KINDS (known: "
            f"{sorted(DEVICE_KINDS)})") from None


# ----------------------------------------------------------------------------
# Table 3 measured operational characterization.
#
# ``throughput`` units: FPS for inference rows, GFLOPS for training rows —
# recorded verbatim from the paper; ``power_w`` is the measured workload power.
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadPoint:
    benchmark: str        # "alexnet" | "vgg16"
    phase: str            # "inference_ternary" | "train_fp32"
    device: str           # key into DEVICES
    throughput: float
    throughput_unit: str  # "FPS" | "GFLOPS"
    power_w: float

    @property
    def efficiency_per_w(self) -> float:
        return self.throughput / self.power_w


TABLE3: Dict[str, WorkloadPoint] = {
    p.benchmark + "/" + p.phase + "/" + p.device: p
    for p in [
        # -- inference, ternary model reduction + PIM (Table 3, top) --
        WorkloadPoint("alexnet", "inference_ternary", "ddr3_pim", 84.8, "FPS", 2.0),
        WorkloadPoint("alexnet", "inference_ternary", "rm_pim", 490.0, "FPS", 0.93),
        # -- training, FP32 (Table 3, bottom) --
        WorkloadPoint("alexnet", "train_fp32", "gpu", 1335.0, "GFLOPS", 21.05),
        WorkloadPoint("alexnet", "train_fp32", "rm_pim", 50.72, "GFLOPS", 5.65),
        WorkloadPoint("alexnet", "train_fp32", "fpga", 34.52, "GFLOPS", 7.74),
        WorkloadPoint("vgg16", "train_fp32", "gpu", 848.0, "GFLOPS", 20.37),
        WorkloadPoint("vgg16", "train_fp32", "rm_pim", 81.95, "GFLOPS", 5.7),
        WorkloadPoint("vgg16", "train_fp32", "fpga", 46.99, "GFLOPS", 7.71),
    ]
}


def workload_points(benchmark: str, phase: str) -> Dict[str, WorkloadPoint]:
    """All Table-3 points for one (benchmark, phase), keyed by device name."""
    out = {}
    for p in TABLE3.values():
        if p.benchmark == benchmark and p.phase == phase:
            out[p.device] = p
    return out


# TPU v5e roofline constants re-exported for the roofline module.
TPU_PEAK_FLOPS = TPU_V5E.peak_flops
TPU_HBM_BW = TPU_V5E.hbm_bw
TPU_LINK_BW = TPU_V5E.link_bw
