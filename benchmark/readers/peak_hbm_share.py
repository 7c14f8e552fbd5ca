"""``peak_bytes_in_use`` of the fullest device over the chip's memory, in
percent."""
from lib import peaks


def read(record, params):
    return 100.0 * record["memory_peak_bytes"] / peaks.peak(
        record["device_kind"], "hbm_bytes")
