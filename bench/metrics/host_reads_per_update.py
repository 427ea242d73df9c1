"""Blocking device-to-host reads per pair update: the ``*.read`` spans of
``repro.obs`` under the ``sweep.pair`` spans of the traced window, over the
number of those pair updates.  None for a program without spans, or for
fewer than 20 pair updates."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    roots = obs.per_root("sweep.pair")
    if len(roots) < 20:
        return None
    return sum(count for r in roots for name, (_, count) in r["spans"].items()
               if name.endswith(".read")) / len(roots)
