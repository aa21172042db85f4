"""Seconds per sweep that the runner spends in the theory-side m_max
predictors, the host pulls of their rows included: the program's
``predict`` spans, one per job that asks for a prediction."""


def read(ctx):
    spans = [e["dur"] for e in ctx["spans"] if e["name"] == "predict"]
    if not spans:
        return None
    return sum(spans) / 1e6 / ctx["sweeps"]
