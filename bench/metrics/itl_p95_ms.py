"""95th percentile of the gaps between consecutive output tokens of
every request.  In an open loop every gap of the window's requests
counts, those after the window closed too; in a closed loop the gaps
that lie inside the window."""
from bench.harness import percentile


def read(run):
    gaps = []
    for r in run.requests:
        ts = r.token_times
        if run.mix["kind"] != "open_loop":
            ts = [t for t in ts if run.window_t0 <= t <= run.window_t1]
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
    return percentile(gaps, 95)
