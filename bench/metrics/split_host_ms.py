"""Median per pair update of the host's time in the truncated split: the
``split`` spans of ``repro.obs`` under each ``sweep.pair`` of the traced
window, in milliseconds.  None for a program without spans, or for fewer
than 20 pair updates."""
import statistics


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    roots = obs.per_root("sweep.pair")
    if len(roots) < 20:
        return None
    return statistics.median(
        r["spans"].get("split", (0, 0))[0] for r in roots) / 1e6
