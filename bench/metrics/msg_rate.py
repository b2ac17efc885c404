"""Messages delivered, over all ranks, per second of the window (from
its opening until the last round's payloads are on the devices)."""


def read(run):
    if not run.rounds:
        return None
    return run.rounds * run.messages_per_round / run.window_s
