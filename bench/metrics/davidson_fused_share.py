"""Share of the traced window's pair updates whose Davidson solve ran its
subspace algebra as fused programs: the ``sweep.pair`` spans of
``repro.obs`` holding at least one ``davidson.fused`` span, in percent.
None for a program without spans, or for fewer than 20 pair updates."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    roots = obs.per_root("sweep.pair")
    if len(roots) < 20:
        return None
    fused = sum(1 for r in roots if r["spans"].get("davidson.fused"))
    return 100.0 * fused / len(roots)
