"""Share, in %, of its roofline that one decode execution reaches: the least
time the chip could take for it, the larger of its operations over the
device kind's bf16 peak and its bytes over the peak HBM bandwidth
(``chipbench/peaks.json``), over the device seconds of one execution of the
module ``jit_serve_decode`` in the traced sub-window (profiler trace,
``XLA Modules`` line).  Operations and bytes are the configuration's own
``decode_flops`` and ``decode_bytes`` of the tokens each decode execution
in the traced sub-window produced, as a mean over those executions: idle
lanes and cache rows past a lane's position count for nothing."""


def read(rec):
    c = rec.counters
    m = (rec.trace or {}).get("modules", {}).get("jit_serve_decode")
    flops_s, bytes_s = c.get("peak_flops_per_s"), c.get("peak_hbm_bytes_per_s")
    steps = c.get("decode_steps")
    if not (m and m[0] and m[1] and flops_s and bytes_s and steps
            and c.get("decode_flops") and c.get("decode_bytes")):
        return None
    least_s = max(c["decode_flops"] / flops_s,
                  c["decode_bytes"] / bytes_s) / steps
    return 100.0 * least_s / (m[1] / m[0])
