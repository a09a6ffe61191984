"""95th percentile over every slab of the window of the time from the
hand-off to ``ingest_grid``/``ingest`` to its return with the state
folded (host clock)."""
import numpy as np


def read(ctx):
    lat = ctx["rec"]["slab_s"]
    return float(np.percentile(lat, 95)) * 1e3 if len(lat) else None
