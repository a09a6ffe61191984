"""Samples of the slabs completed in the window, over the time from the
window's start to the last completion (host clock)."""


def read(ctx):
    rec = ctx["rec"]
    if not rec.get("slabs"):
        return None
    return rec["samples"] / (rec["done"][-1] - rec["start"])
