"""The chips a run measures, their published peaks, and their memory."""

from __future__ import annotations

import pathlib

from bench.lib.spec import BENCH_DIR, read_json


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def accelerator(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator` when JAX
    runs on anything else (the benchmark never falls back to the CPU)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def peaks(root: pathlib.Path, device_kind: str) -> dict:
    """``{"bf16_flops": ..., "hbm_bytes_per_s": ..., "hbm_bytes": ...}`` of one
    chip of ``device_kind``, from ``bench/peaks.json``.  An unknown kind is an
    error, never a default."""
    table = read_json(pathlib.Path(root) / BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip, where the backend reports it."""
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
