"""Device milliseconds per dashboard refresh: the device time under the
``refresh`` span (the series kernels, the history tier's energy-at
kernel and their transfers), from the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["span_count"].get("refresh") \
            or not tr["device_s"]["refresh"]:
        return None
    return tr["device_s"]["refresh"] / tr["span_count"]["refresh"] * 1e3
