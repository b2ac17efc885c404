"""Device time of the collective-permute operations that LCX's puts
lower to, per round (one execution of ``jit_ring_round``), averaged over
the chips of the traced window."""
from bench import tracing


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    per = []
    for dev in run.trace.devices:
        _, rounds = tracing.program_time(run.trace, dev, "jit_ring_round")
        secs, n = tracing.op_time(run.trace, dev,
                                  lambda s: "collective-permute" in s)
        if rounds and n:
            per.append(secs / rounds * 1e6)
    return sum(per) / len(per) if per else None
