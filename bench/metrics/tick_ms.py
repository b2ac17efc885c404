"""Mean host time of one ``ServingEngine.tick()`` in the traced window:
the ticks' total over their number."""


def read(run):
    ticks = [t for t in run.ticks if t.traced]
    if not ticks:
        return None
    return sum(t.t1 - t.t0 for t in ticks) / len(ticks) * 1e3
