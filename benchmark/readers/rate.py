"""A count of the whole window over the whole window's seconds."""


def read(record, params):
    s = record["scalars"]
    return s[params["count"]] / s[params["over"]]
