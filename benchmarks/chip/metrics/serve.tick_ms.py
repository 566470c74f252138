"""Mean host time of one engine or router ``step()`` in the window, in ms.

A step admits and prefills, dispatches the decode and blocks on sampling, so
this is the time per token of every lane.  Host clock around each step:
the sum over the window divided by the number of steps."""


def read(rec):
    steps = rec.spans.get("step", [])
    return 1e3 * sum(steps) / len(steps) if steps else None
