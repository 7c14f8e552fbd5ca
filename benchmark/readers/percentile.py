"""A percentile over every sample of the window (numpy's linear rule)."""
import numpy as np


def read(record, params):
    xs = record["samples"].get(params["samples"])
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), params["q"]))
