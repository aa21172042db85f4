"""Seconds per sweep that the runner spends outside its jobs: datasets,
characters, readouts, predictors, journal and artifact store.  The
program's ``sweep`` spans less their ``job`` children."""


def read(ctx):
    spans = ctx["spans"]
    sweep = sum(e["dur"] for e in spans if e["name"] == "sweep")
    jobs = sum(e["dur"] for e in spans if e["name"] == "job")
    if not sweep:
        return None
    return (sweep - jobs) / 1e6 / ctx["sweeps"]
