"""The chip: its published peaks and the check that one is present.

The peaks are the benchmark's own copy, keyed by ``device_kind``, so no
change to the program can move the yardstick. A device that is not in the
table is an error, not a default.
"""

from __future__ import annotations

import sys

_V5E = ("Google Cloud documentation, 'TPU v5e' "
        "(cloud.google.com/tpu/docs/v5e)")

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # 197 TFLOP/s bf16
        "int8_ops": 393e12,        # 393 TOP/s int8
        "hbm_bw": 819e9,           # 819 GB/s
        "hbm_bytes": 16 * 2 ** 30,
        "source": _V5E,
    },
}


def fail(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def require(chips: int) -> dict:
    """The accelerator as JAX reports it; exits non-zero without one."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX finds no accelerator: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if devs[0].device_kind not in PEAKS:
        fail(f"no peaks for device_kind {devs[0].device_kind!r}")
    return describe(devs[:chips])


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))
