"""Seconds per sweep of the runner's characters pass, host and device
alike (the Eq. 3 C_sim and ``MX.summarize`` return host floats): the
program's ``characters`` spans, one per dataset."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["name"] == "characters"]
    if not spans:
        return None
    return sum(spans) / 1e6 / ctx["sweeps"]
