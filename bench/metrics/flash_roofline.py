"""The Pallas flash-attention kernel's share of its roofline: for every
prefill in the traced window, one causal call per layer, whose least
time is the larger of its operations over the bf16 peak and its bytes
(q, k, v read, the output written) over the bandwidth; summed, over the
kernel's device time in the trace."""
from bench import flops, tracing


def is_flash(name: str) -> bool:
    # an op is named by its HLO text; the kernel is a Mosaic custom call,
    # the only one in the programs of the cells that list this metric
    return "flash" in name.lower() or "tpu_custom_call" in name


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    dev = sorted(run.trace.devices)[0]
    secs, n = tracing.op_time(run.trace, dev, is_flash)
    lens = [s for t in run.ticks if t.traced for s in t.prefill_lens]
    if not n or not lens or not secs:
        return None
    sp = run.spec
    pk = flops.peaks(run.device_kind)
    least = 0.0
    for s in lens:
        ops, nbytes = flops.flash_cost(sp.num_attention_heads,
                                       sp.num_key_value_heads, sp.head_dim, s)
        least += sp.num_hidden_layers * max(ops / pk["bf16_flops"],
                                            nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
