"""Share of the KV-cache pool that the decode steps of the traced window
attend: the cache rows of every advanced slot (``Tick.kv_rows``), over
the rows the pool holds (``n_slots`` x ``max_seq``) times the decode
steps."""


def read(run):
    ticks = [t for t in run.ticks if t.traced and t.decoded]
    if not ticks:
        return None
    serve = run.config["serve"]
    pool = serve["n_slots"] * serve["max_seq"]
    return 100.0 * sum(t.kv_rows for t in ticks) / (len(ticks) * pool)
