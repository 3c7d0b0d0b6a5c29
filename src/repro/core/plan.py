"""Bin-range planning.

The paper's §3 shows software PB must *compromise* on a single bin-range
knob; COBRA's §4 removes the knob by deriving a per-cache-level bin range
from architectural capacities. We reproduce both:

  * ``compromise_bin_range``  — the single-knob software-PB choice.
  * ``CobraPlan.from_hardware`` — the knob-free hierarchical plan, driven
    by an explicit hardware model (TPU: VMEM is the only fast level, so
    the hierarchy is realized as multiple VMEM-bounded radix *passes*;
    at pod scale an outermost ICI level is added by the distributed
    dispatch path).

All sizes in bytes. Int32 tuple elements assumed (paper uses 32-bit ids).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Sequence


@dataclass(frozen=True)
class HardwareModel:
    """Capacities that bound C-Buffer fan-out per level.

    The CPU default mirrors the paper's Xeon (32K L1 / 35M LLC); the TPU
    default models a v5e core. ``cbuffer_bytes`` is the unit of coalesced
    transfer: a cacheline on CPU, a (8,128)-lane int32 tile on TPU.
    """

    name: str
    fast_levels: Sequence[int]  # capacity of each fast level, small -> large
    cbuffer_bytes: int
    dram_bandwidth: float  # bytes/s, for the traffic->time model
    fast_bandwidth: float  # bytes/s of the innermost level

    @staticmethod
    def cpu_xeon() -> "HardwareModel":
        return HardwareModel(
            name="xeon-14c",
            fast_levels=(32 * 1024, 1024 * 1024, 35 * 1024 * 1024),
            cbuffer_bytes=64,
            dram_bandwidth=60e9,
            fast_bandwidth=1000e9,
        )

    @staticmethod
    def tpu_v5e() -> "HardwareModel":
        # One fast level (VMEM, 128 MiB per core) but multiple *passes*
        # give the hierarchy. The level is budgeted at half of VMEM, and
        # that one number is also the ``vmem_limit_bytes`` the compiled
        # fused kernel is built with (core/executor.py, kernels/fused.py).
        return HardwareModel(
            name="tpu-v5e",
            fast_levels=(64 * 1024 * 1024,),
            cbuffer_bytes=8 * 128 * 4,  # one int32 VREG tile
            dram_bandwidth=819e9,
            fast_bandwidth=20e12,  # VMEM
        )

    @staticmethod
    def for_device_kind(kind: str) -> "HardwareModel":
        """The model of a TPU by its ``device_kind``. A kind that is not
        in the table is an error, never an assumed v5e."""
        models = {"TPU v5 lite": HardwareModel.tpu_v5e}
        if kind not in models:
            raise ValueError(
                f"no HardwareModel for TPU device_kind {kind!r} "
                f"(known: {sorted(models)})"
            )
        return models[kind]()

    @staticmethod
    def attached() -> "HardwareModel":
        """The model of the attached device: a TPU by its ``device_kind``
        (``for_device_kind``), any other backend models the v5e the
        repo targets."""
        import jax

        dev = jax.devices()[0]
        if dev.platform == "tpu":
            return HardwareModel.for_device_kind(dev.device_kind)
        return HardwareModel.tpu_v5e()


TUPLE_BYTES = 8  # (index, value) int32 pairs, as in the paper

# Geometry of the compiled fused C-Buffer kernels (kernels/fused.py):
# 2048-index bins (16 accumulator lane rows each) and 1024-tuple
# C-Buffers (8 lane rows, one (8, 128) tile to transpose per flush).
# ``fused_vmem_bytes`` is the kernel's footprint at this geometry, and
# the one VMEM budget is the fast level of the ``HardwareModel``: fused
# legality (``fused_fits``), the F-tile policy, the realization and the
# compiled ``vmem_limit_bytes`` all compare against it.
FUSED_BIN_RANGE = 2048
FUSED_CAP = 1024
# Indices per SMEM block (the TPU lays 1-D int32 arrays out in
# 1024-word tiles, so blocks are multiples of 1024).
FUSED_BLOCK = 2048
# Mosaic's own scratch beside the kernel's buffers (vreg spills, the
# flush's transposed tiles).
_FUSED_SCRATCH_BYTES = 1 << 20


def fused_vmem_bytes(
    num_indices: int,
    *,
    bin_range: int = FUSED_BIN_RANGE,
    cap: int = FUSED_CAP,
    itemsize: int = 4,
    f_tile: int = 0,
) -> int:
    """VMEM the fused kernel keeps resident: accumulator, C-Buffer
    offsets and values, with the TPU's (8, 128) tile padding, plus the
    flush's working tiles. ``f_tile <= 1`` is the scalar kernel (a width-1
    tile of a row block runs it once per column); ``f_tile > 1`` the
    row-block kernel at that F-tile, whose ``add`` flush also holds a
    one-hot tile and a partial sum of the bin's accumulator."""
    lanes = 128
    nb = math.ceil(max(1, num_indices) / bin_range)
    rows_per_buffer = math.ceil(cap // lanes / 8) * 8
    offsets = nb * rows_per_buffer * lanes * 4
    if f_tile > 1:
        cols = math.ceil(f_tile / lanes) * lanes
        rows = math.ceil(bin_range / 8) * 8
        resident = nb * (rows + cap) * cols * itemsize
        flush = rows * (lanes + 2 * cols) * 4
        return offsets + resident + flush + _FUSED_SCRATCH_BYTES
    acc = nb * math.ceil(math.ceil(bin_range / lanes) / 8) * 8 * lanes
    values = nb * rows_per_buffer * lanes
    return offsets + (acc + values) * itemsize + _FUSED_SCRATCH_BYTES


def fused_fits(
    hw: HardwareModel,
    num_indices: int,
    *,
    bin_range: int = FUSED_BIN_RANGE,
    itemsize: int = 4,
    f_tile: int = 0,
) -> bool:
    """Fusion legality, capacity half (DESIGN.md §8.1): the fused
    kernel's resident footprint fits the largest fast level (on a TPU
    the only one, VMEM, which is also the limit the kernel compiles
    with)."""
    need = fused_vmem_bytes(
        num_indices, bin_range=bin_range, itemsize=itemsize, f_tile=f_tile
    )
    return need <= hw.fast_levels[-1]


def num_bins_for_range(num_indices: int, bin_range: int) -> int:
    return max(1, math.ceil(num_indices / bin_range))


def binread_optimal_range(hw: HardwareModel, value_bytes_per_index: int = 8) -> int:
    """Bin-Read wants each bin's touched index range resident in the
    innermost fast level (paper Fig. 3 right).  value_bytes_per_index
    counts the arrays indexed during apply (offsets+neighs ~ 8B)."""
    return max(1, hw.fast_levels[0] // (2 * value_bytes_per_index))


def binning_optimal_num_bins(hw: HardwareModel) -> int:
    """Binning wants all C-Buffers resident in the innermost fast level
    (paper Fig. 3 left)."""
    return max(2, hw.fast_levels[0] // (2 * hw.cbuffer_bytes))


def compromise_bin_range(num_indices: int, hw: HardwareModel) -> int:
    """The single-knob software-PB compromise: geometric mean of the two
    phases' optima, clamped. This reproduces the paper's observation that
    neither phase runs at its best point."""
    r_read = binread_optimal_range(hw)
    r_bin = max(1, math.ceil(num_indices / binning_optimal_num_bins(hw)))
    return int(max(1, math.sqrt(r_read * r_bin)))


@dataclass(frozen=True)
class CobraPlan:
    """A knob-free hierarchical binning plan.

    ``level_fanouts[k]`` is the number of child bins each level-k bin is
    split into on pass k (COBRA: Y_1 coarse ... Y_L fine). The product of
    fan-outs equals the final number of bins; the final bin range is the
    Bin-Read-optimal range, so Bin-Read runs at its best point while each
    Binning pass runs with a fan-out whose C-Buffers fit the fast level —
    Binning's best point. That is exactly the paper's Fig. 4 claim.

    Hashable (fan-outs stored as a tuple) so jitted builders can cache on
    the plan.
    """

    num_indices: int
    final_bin_range: int
    level_fanouts: Tuple[int, ...] = ()

    @property
    def num_bins(self) -> int:
        return num_bins_for_range(self.num_indices, self.final_bin_range)

    @property
    def num_passes(self) -> int:
        return len(self.level_fanouts)

    def level_ranges(self) -> List[int]:
        """Bin range after each pass (coarse -> fine). Ranges are nested
        multiples of the final range (paper's 16R / 8R / R): pass k's
        range = final_range x prod(fanouts after k), so every coarse bin
        is a whole number of fine bins — the property that makes the
        stable multi-pass composition equal a single stable fine sort."""
        ranges = []
        for k in range(len(self.level_fanouts)):
            mult = 1
            for y in self.level_fanouts[k + 1 :]:
                mult *= y
            ranges.append(self.final_bin_range * mult)
        return ranges

    @staticmethod
    def from_hardware(
        num_indices: int,
        hw: HardwareModel | None = None,
        value_bytes_per_index: int = 8,
        max_fanout: int | None = None,
        final_bin_range: int | None = None,
    ) -> "CobraPlan":
        """Derive the knob-free plan (paper §4.2). ``final_bin_range``
        overrides the Bin-Read-optimal range when a consumer needs bins at
        a specific granularity (e.g. a pre-binned PageRank loop)."""
        hw = hw or HardwareModel.attached()
        final_range = final_bin_range or min(
            binread_optimal_range(hw, value_bytes_per_index), num_indices
        )
        final_range = max(1, min(final_range, num_indices))
        total_bins = num_bins_for_range(num_indices, final_range)
        per_pass = max_fanout or binning_optimal_num_bins(hw)
        fanouts: List[int] = []
        remaining = total_bins
        while remaining > 1:
            y = min(per_pass, remaining)
            fanouts.append(y)
            remaining = math.ceil(remaining / y)
        if not fanouts:
            fanouts = [1]
        return CobraPlan(
            num_indices=num_indices,
            final_bin_range=final_range,
            level_fanouts=tuple(fanouts),
        )
