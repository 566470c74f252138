"""The model's share of the chips' bf16 peak, in %: the operations that the
prompt and output tokens processed in the window need (the configuration's
own ``prefill_flops`` and ``decode_flops``), over the window's seconds, the
number of chips and the peak of the device kind (``chipbench/peaks.json``)."""


def read(rec):
    c = rec.counters
    peak = c.get("peak_flops_per_s")
    if not peak or not c.get("model_flops") or rec.window_s <= 0:
        return None
    return 100.0 * c["model_flops"] / (rec.window_s * c["chips"] * peak)
