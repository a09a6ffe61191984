"""Host milliseconds per dashboard refresh in the serving layer: the
``refresh`` span (the query flush) less the device time under it, from
the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["span_count"].get("refresh"):
        return None
    return ((tr["span_s"]["refresh"] - tr["device_s"]["refresh"])
            / tr["span_count"]["refresh"] * 1e3)
