"""Device milliseconds per slab under the ``ingest`` span (the ingest
kernel and its transfers), from the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["span_count"].get("ingest") \
            or not tr["device_s"]["ingest"]:
        return None
    return tr["device_s"]["ingest"] / tr["span_count"]["ingest"] * 1e3
