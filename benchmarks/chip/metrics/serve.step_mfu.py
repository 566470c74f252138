"""The whole serving step's share of the chips' bf16 peak, in %, over the
traced sub-window that the decode program's roofline share reads: the
operations that the prompt and output tokens stamped in it need (the
configuration's own ``prefill_flops`` and ``decode_flops``), over its
seconds on the host's clock, the number of chips and the peak of the
device kind (``chipbench/peaks.json``).  The sub-window leaves out the
profiler's start and stop, which stall the rest of a traced window."""


def read(rec):
    c = rec.counters
    peak, s = c.get("peak_flops_per_s"), c.get("traced_s")
    if not peak or not s or not c.get("traced_flops"):
        return None
    return 100.0 * c["traced_flops"] / (s * c["chips"] * peak)
