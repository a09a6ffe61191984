"""Share of its roofline that serving reaches: the bytes the traced
refreshes' fleet series need from their shapes
(``bench/shapes_history.py``) over the chip's HBM bandwidth, divided by
the device time under the ``refresh`` spans.  Bandwidth bounds it: the
series does a few operations per byte of the history tier."""


def read(ctx):
    tr, peak = ctx["trace"], ctx["peaks"]
    if not tr or not peak or not ctx["rec"].get("series_bytes") \
            or not tr["device_s"].get("refresh"):
        return None
    need_s = ctx["rec"]["series_bytes"] / peak["hbm_bytes_per_s"]
    return need_s / tr["device_s"]["refresh"] * 100.0
