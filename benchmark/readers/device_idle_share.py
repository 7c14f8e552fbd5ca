"""1 - union of the device-op intervals over the traced slice, in percent
(a device's mean where there are several)."""


def read(record, params):
    trace = record.get("trace")
    return None if trace is None else 100.0 * trace.idle_share()
