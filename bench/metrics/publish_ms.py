"""Host milliseconds per dashboard refresh spent publishing the
snapshot it reads: the ``publish`` span less the device time under it,
from the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["span_count"].get("publish"):
        return None
    return ((tr["span_s"]["publish"] - tr["device_s"]["publish"])
            / tr["span_count"]["publish"] * 1e3)
