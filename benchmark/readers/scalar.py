"""One number the driver recorded, as it is."""


def read(record, params):
    return record["scalars"].get(params["key"])
