"""Share of its roofline that the ingest layer's device time reaches:
the bytes the traced slabs' work needs from their shapes
(``bench/shapes.py``) over the chip's HBM bandwidth, divided by the
device time under the ``ingest`` spans.  Bandwidth bounds it: the fold
does a few operations per byte."""


def read(ctx):
    tr, peak = ctx["trace"], ctx["peaks"]
    if not tr or not peak or not tr["device_s"].get("ingest"):
        return None
    need_s = ctx["rec"]["ingest_bytes"] / peak["hbm_bytes_per_s"]
    return need_s / tr["device_s"]["ingest"] * 100.0
