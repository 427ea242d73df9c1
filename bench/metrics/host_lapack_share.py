"""Share of the window spent in the host LAPACK callback of the float64
decomposition (``repro.dist.decomp.host_svd_stats`` seconds over the
window's host-clock length)."""


def read(run):
    if run.get("host_lapack_s") is None:
        return None
    return 100.0 * run["host_lapack_s"] / run["window_s"]
