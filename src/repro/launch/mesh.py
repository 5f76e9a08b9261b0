"""Production mesh definitions (TPU v5e).

single pod : (data=16, model=16)        = 256 chips
multi-pod  : (pod=2, data=16, model=16) = 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run forces 512 host devices BEFORE any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first data*model local devices. Fewer
    devices than asked for is an error: a mesh quietly shrunk to fit
    would run a different program than the one requested."""
    devices = jax.devices()
    if data * model > len(devices):
        raise ValueError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"devices, {len(devices)} found")
    return _mk_mesh((data, model), ("data", "model"),
                    devices=devices[:data * model])


# Per-chip peaks, keyed by jax's ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table row of one device kind; a kind the table does not
    list is an error, never a silent v5e default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no peak figures for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source")
    return PEAKS[device_kind]
