"""Decode's share of the chip's memory bandwidth: the bytes the decode
steps of the traced window need (every weight, and each advanced slot's
cache rows up to its length, as the cell's architecture counts them)
over the device time of ``jit_decode_slots`` in the trace, times the
peak bandwidth."""
from bench import flops, tracing


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    dev = sorted(run.trace.devices)[0]
    secs, n = tracing.program_time(run.trace, dev, "jit_decode_slots")
    ticks = [t for t in run.ticks if t.traced and t.decoded]
    if not n or not ticks:
        return None
    need = sum(run.arch.decode_bytes(run.spec, t.kv_rows) for t in ticks)
    bw = flops.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / (secs * bw)
