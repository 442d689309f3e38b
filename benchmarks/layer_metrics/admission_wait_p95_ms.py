"""HTTP + admission: 95th percentile of the admission wait (see
``admission_wait_mean_ms``) of the requests admitted in the window, as the
upper edge of the bucket of ``engine.admission_wait`` that holds it, ms
(edges 1, 2, 5 ... 5000; beyond the last edge it reads the last edge, i.e.
"at least 5000")."""
from benchmarks.harness.registry import load_module


def p95_upper_edge(le_ms, counts, pct=95):
    """Upper edge of the bucket holding the ``pct``-th percentile of a
    histogram with upper edges ``le_ms`` and one overflow bucket after
    them: the first bucket at which the cumulative count reaches the
    nearest rank, ceil(pct * n / 100). None of an empty histogram."""
    n = sum(counts)
    if n <= 0:
        return None
    rank = -(-pct * n // 100)  # ceil, in whole numbers
    seen = 0
    for edge, c in zip(list(le_ms) + [le_ms[-1]], counts):
        seen += c
        if seen >= rank:
            return float(edge)
    return float(le_ms[-1])


def read(ctx):
    d = load_module("layer_metrics", "admission_wait_mean_ms").delta(ctx)
    return None if d is None else p95_upper_edge(d[3], d[2])
