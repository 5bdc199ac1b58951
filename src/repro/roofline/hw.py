"""Device facts, one table keyed by ``jax.devices()[0].device_kind``.

Every number carries its source. A device kind that is not in the table is
an error, never a default: a roofline share computed against the wrong
chip's peaks is worse than none.

``TARGET_KIND`` is the chip this repo is built and benchmarked for (TPU
v5e reports ``"TPU v5 lite"``); the module-level constants below are its
row, for the analytic models that run without a device.
"""

from __future__ import annotations

import dataclasses

__all__ = ["DeviceSpec", "DEVICES", "TARGET_KIND", "spec"]


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    bf16_flops: float      # peak FLOP/s per chip
    int8_ops: float        # peak OP/s per chip
    hbm_bw: float          # bytes/s per chip
    hbm_bytes: int         # per chip
    ici_link_bw: float     # bytes/s per inter-chip link
    vmem_bytes: int        # per TensorCore (v5e: one TensorCore per chip)
    source: str


_V5E_PAGE = "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)"

DEVICES = {
    "TPU v5 lite": DeviceSpec(
        bf16_flops=197e12,             # v5e page: 197 TFLOP/s bf16
        int8_ops=393e12,               # v5e page: 393 TOP/s int8
        hbm_bw=819e9,                  # v5e page: 819 GB/s
        hbm_bytes=16 * 2 ** 30,        # v5e page: 16 GiB HBM2
        ici_link_bw=1600e9 / 8 / 4,    # v5e page: 1,600 Gbit/s ICI over 4 links
        vmem_bytes=128 * 2 ** 20,      # jax 0.9.0 pallas.tpu.get_tpu_info()
        source=_V5E_PAGE + "; VMEM from jax 0.9.0 "
                           "jax.experimental.pallas.tpu.get_tpu_info()"),
}

TARGET_KIND = "TPU v5 lite"


def spec(device_kind: str) -> DeviceSpec:
    try:
        return DEVICES[device_kind]
    except KeyError:
        raise KeyError(f"no device facts for device_kind {device_kind!r}; "
                       f"known: {sorted(DEVICES)}") from None


_T = spec(TARGET_KIND)
PEAK_BF16_FLOPS = _T.bf16_flops
PEAK_INT8_OPS = _T.int8_ops
HBM_BW = _T.hbm_bw
ICI_LINK_BW = _T.ici_link_bw
HBM_BYTES = _T.hbm_bytes
VMEM_BYTES = _T.vmem_bytes

# effective per-link traffic multiplier by collective type (ring algorithms)
RING_FACTOR = {
    "all-reduce": 2.0,        # reduce-scatter + all-gather phases
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
