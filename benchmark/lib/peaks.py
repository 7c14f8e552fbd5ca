"""Per-chip peaks keyed by jax's ``device_kind``.

A kind that is not here is an error, not a default: a share of the wrong peak
is a wrong number. (The FLOP/s entry is copied from ``bench.PEAKS``; the
bandwidth and memory entries are new.)
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peak(device_kind, what):
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise SystemExit(
            "no peak %r on record for device kind %r (known kinds: %s): add "
            "it to benchmark/lib/peaks.py with its source"
            % (what, device_kind, sorted(PEAKS)))
