"""95th percentile of the benchmark's span between consecutive pair-update
callbacks (``on_site``), in milliseconds."""

import statistics


def read(run):
    d = run.get("update_s") or []
    if len(d) < 20:
        return None
    return 1000.0 * statistics.quantiles(d, n=20)[18]
