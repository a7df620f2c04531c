"""tpot_p95_ms: 95th percentile, over every emission in the window after a
request's first, of the host time since that request's previous emission
divided by the tokens the emission committed."""
from bench.harness import percentile


def read(run):
    w0, w1 = run.window
    gaps = []
    for r in run.requests:
        for (t_prev, _), (t, n) in zip(r.emissions, r.emissions[1:]):
            if w0 < t <= w1 and n > 0:
                gaps.append((t - t_prev) / n * 1e3)
    return percentile(gaps, 95) if gaps else None
