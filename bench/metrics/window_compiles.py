"""Programs built inside the window (compiled, or loaded from the
persistent cache): every one is a block structure set-up did not warm."""


def read(run):
    return run.get("window_programs")
