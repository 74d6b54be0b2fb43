"""What every entry needs of the device, whatever the model: the first
claim of the chips and the reading of their memory counters."""

from __future__ import annotations


def claim_devices(chips: int, require_platform: str | None) -> dict:
    """This process's first act with JAX: point the compile cache at its
    one place, take the devices, and refuse the wrong platform before any
    work is done."""
    from distkeras_tpu.utils.platform import use_compile_cache

    use_compile_cache()
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_platform and (info["platform"] != require_platform
                             or len(devices) < chips):
        raise SystemExit(f"chipbench: needs {chips} {require_platform} "
                         f"device(s), found {info}: refusing to run")
    return info


def memory_peak_bytes(stats: dict) -> int:
    """Peak device memory from ``device.memory_stats()``. On the TPU
    ``peak_bytes_in_use`` counts live arrays only; what the loaded programs
    reserve for their temporaries is ``peak_bytes_reserved`` (for the
    bert-base step 11.0 GB beside 1.5 GB in use, against the compiler's own
    1.31 + 11.07 GB: my chip run, PR 24). The peak is their sum."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))
