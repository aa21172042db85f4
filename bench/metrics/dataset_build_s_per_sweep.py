"""Seconds per sweep that the runner spends building and splitting its
datasets (the LS sequences are on-device scans): the program's
``dataset_build`` spans, one per dataset's build and one per split."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["name"] == "dataset_build"]
    if not spans:
        return None
    return sum(spans) / 1e6 / ctx["sweeps"]
