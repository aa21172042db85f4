"""Seconds per sweep that the sharded grid spends copying each bucket's
dataset and draw arguments to every chip of the mesh, until the copies
land: the program's ``replicate`` spans, one per bucket.  A sweep on one
chip, or a program without the span, gives nothing to read."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["name"] == "replicate"]
    if not spans:
        return None
    return sum(spans) / 1e6 / ctx["sweeps"]
