"""The device a chip-expecting entry point actually got.

Kernel entries pick interpret mode off-TPU and the kernel-mode switches pick
the XLA paths there — right for tests, which force the CPU on purpose. But a
process that was MEANT to have the chip and got the CPU would carry on and
print rates of XLA's CPU backend under device-metric names. Every script that
reports a time, a rate or a share of a peak calls ``require_tpu()`` once,
before any work: it names the device on the result and refuses to run
anywhere else.
"""

from __future__ import annotations

# Published per-chip peaks, keyed by the ``device_kind`` jax reports. A share
# of a peak is only ever computed against the row of the device the process
# actually sees; a kind that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e" (system '
                  'architecture: per-chip peak compute and HBM bandwidth)',
    },
}


def device_triple() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports them. Initializes
    the backend, so this process then owns the chip."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """The device triple, or RuntimeError when the platform is not ``tpu``
    — a measurement path that finds no chip fails; it never falls back."""
    dev = device_triple()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"this entry point reports device metrics and needs a TPU, but "
            f"jax sees {dev['count']} x {dev['kind']!r} on platform "
            f"{dev['platform']!r}; a number from this backend would not be "
            f"a device metric (tests force the CPU explicitly and do not "
            f"come through here)")
    return dev


def memory_line(when: str) -> str | None:
    """One narration line with every local device's ``memory_stats()``
    bytes in use and peak — how a run shows what it holds on the chip and
    that a sharded tree is spread across chips, not parked on device 0.
    None where the backend reports no stats (the CPU backend)."""
    import json

    import jax

    rows = []
    for d in jax.local_devices():
        st = d.memory_stats()
        if not st:
            return None
        rows.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                     "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return f"💡 device memory ({when}): {json.dumps(rows)}"


def peak(kind: str, metric: str) -> float:
    """One published peak of device ``kind`` (a ``PEAKS`` row key)."""
    if kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {kind!r} "
            f"(known: {sorted(PEAKS)}); add a sourced row to "
            f"utils/chip.PEAKS before reporting a share of a peak on it")
    return PEAKS[kind][metric]
