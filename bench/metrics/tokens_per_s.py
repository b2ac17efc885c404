"""Output tokens produced inside the window over the window's seconds
(from its opening to the end of its last tick)."""


def read(run):
    if not run.ticks:
        return None
    n = sum(1 for r in run.requests for t in r.token_times
            if run.window_t0 <= t <= run.window_t1)
    return n / run.window_s
