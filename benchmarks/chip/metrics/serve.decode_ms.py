"""Mean time, in ms, from a decode dispatch to its sampled tokens on the
host: the program's ``decode`` intervals in the window."""


def read(rec):
    d = rec.program_spans.get("decode")
    return 1e3 * sum(d) / len(d) if d else None
