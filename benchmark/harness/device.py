"""The chip: its published peaks and how the harness insists on it."""

from __future__ import annotations

# Published peaks of ONE chip by the exact ``device_kind`` jax reports:
# (dense bf16 FLOP/s, HBM bytes/s). Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s; ``device_kind``
# as read on the chip (PR 21). The benchmark's own copy of the program's
# utils/flops.DEVICE_PEAKS: a later PR cannot move a utilisation by
# editing the program's table.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class NoChip(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoChip` on any
    other backend, and ``KeyError`` for a TPU without published peaks."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(f"needs a TPU, but jax found platform "
                     f"{first.platform!r} ({first.device_kind!r} x "
                     f"{len(devices)})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found "
                     f"{len(devices)}")
    if first.device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{first.device_kind!r}; add it to harness/device.py "
                       f"with its source (known: {sorted(PEAKS)})")
    return devices[:chips]


def open_chips(chips: int):
    """What every entry point does before its first jit: ``(devices,
    cache_dir)``. The persistent compile cache lives at a fixed path inside
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says); programs
    that compile in under a second are kept too, so that a warm run
    compiles nothing. Raises :class:`NoChip` off a TPU, before the
    program is imported."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = require_chips(chips)
    from distmlip_tpu.utils.compile_cache import enable_compile_cache

    return devices, enable_compile_cache()


def identity(devices) -> dict:
    return {"platform": str(devices[0].platform),
            "kind": str(devices[0].device_kind), "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest device: the allocator's peak of live
    buffers plus what the runtime reserved for the compiled programs'
    temporaries. On a TPU a step's temporaries are not in
    ``peak_bytes_in_use``: a MACE step whose program needs 2 GB of them
    reads 0.3 GB there and 2.0 GB under ``peak_bytes_reserved`` (PERF.md
    section 2). 0 on a backend that reports nothing: the CPU of the
    tests."""
    def peak(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in devices)
