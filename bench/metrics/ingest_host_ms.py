"""Host milliseconds per slab in the ingest layer: the ``ingest`` span
less the device time under it, from the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["span_count"].get("ingest"):
        return None
    return ((tr["span_s"]["ingest"] - tr["device_s"]["ingest"])
            / tr["span_count"]["ingest"] * 1e3)
