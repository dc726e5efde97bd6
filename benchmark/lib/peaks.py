"""Published per-chip peaks, keyed by the exact `device_kind` string JAX
reports. A device that is not in the table is an error, never a default:
a wrong peak makes every share computed from it wrong without a sign.

Copied from the program's `train/metrics.CHIP_SPECS` (PERF.md lists the
original under Open questions); later PRs may change the program, not the
yardstick.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoChip(RuntimeError):
    """No accelerator of a known kind, or fewer chips than the cell asks."""


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise NoChip(f"device_kind {device_kind!r} is not in the benchmark's "
                     f"peaks table (known: {sorted(PEAKS)}); add its "
                     "published peaks in a benchmark PR")
    return PEAKS[device_kind]


def require_chips(n_chips: int) -> dict:
    """The device record of this process, or NoChip: the benchmark never
    reports a number from a CPU or from fewer chips than the cell needs."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform == "cpu":
        raise NoChip("JAX found no accelerator (platform 'cpu'); the "
                     "benchmark reports no number from a CPU run")
    peaks_for(dev.device_kind)
    if len(devs) < n_chips:
        raise NoChip(f"the cell asks for {n_chips} chips, JAX sees "
                     f"{len(devs)}")
    return device_record()


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak device memory on the fullest local chip: peak_bytes_in_use +
    peak_bytes_reserved. This runtime keeps a compiled program's
    temporaries in a reserved region that peak_bytes_in_use leaves out
    (PERF.md, PR 21 finding 5: their sum matched the compiler's argument +
    temp bytes within 1%). None where the backend reports nothing (CPU)."""
    import jax
    best = None
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = st.get("peak_bytes_in_use") or st.get("bytes_in_use")
        if not peak:
            continue
        peak += st.get("peak_bytes_reserved") or 0
        best = peak if best is None else max(best, peak)
    return best
