"""Davidson iterations per two-site update in the window (``SweepStats``
and the sweep's resume-state accumulators)."""


def read(run):
    if not run.get("solves"):
        return None
    return run["iterations"] / run["solves"]
