"""Host time per engine tick not spent waiting on the device, in ms: the
program's ``tick`` spans minus its ``fetch`` spans (every wait for sampled
ids), over the number of ticks in the window."""


def read(rec):
    ticks = rec.program_spans.get("tick")
    if not ticks:
        return None
    fetch = rec.program_spans.get("fetch", [])
    return 1e3 * (sum(ticks) - sum(fetch)) / len(ticks)
