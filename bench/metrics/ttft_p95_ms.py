"""95th percentile of time to first token over every request of the
window, each timed from its scheduled arrival to the end of the tick
that produced its first token (an open loop's requests only)."""
from bench.harness import percentile


def read(run):
    if run.mix["kind"] != "open_loop":
        return None
    return percentile([(r.token_times[0] - r.arrival) * 1e3
                       for r in run.requests if r.token_times], 95)
