"""Host time of prefill per prompt token, in us: the program's ``prefill``
spans (dispatch to first tokens on the host) over its ``prefill_tokens``
count, both in the window."""


def read(rec):
    pre = rec.program_spans.get("prefill")
    n = rec.program_counters.get("prefill_tokens")
    return 1e6 * sum(pre) / n if pre and n else None
