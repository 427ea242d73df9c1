"""Median per pair update of the host's time blocked on device reads: every
``*.read`` span of ``repro.obs`` under each ``sweep.pair`` of the traced
window, summed, in milliseconds.  None for a program without spans, or for
fewer than 20 pair updates."""
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
        sum(ns for name, (ns, _) in r["spans"].items()
            if name.endswith(".read")) for r in roots) / 1e6
