"""The whole prefill step's share of the chip's peak: the model
operations of the prompts admitted in the traced window (matmuls, causal
attention, the last position's head, as the cell's architecture counts
them) over the device time of
``jit_prefill_slot`` in the trace, times the bf16 peak."""
from bench import flops, tracing


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    dev = sorted(run.trace.devices)[0]
    secs, n = tracing.program_time(run.trace, dev, "jit_prefill_slot")
    lens = [s for t in run.ticks if t.traced for s in t.prefill_lens]
    if not n or not lens:
        return None
    ops = sum(run.arch.prefill_flops(run.spec, s) for s in lens)
    return 100.0 * ops / (secs * flops.peaks(run.device_kind)["bf16_flops"])
