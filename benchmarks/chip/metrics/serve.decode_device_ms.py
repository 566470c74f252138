"""Device time of one decode program, in ms: the seconds of the module
``jit_serve_decode`` on the device over its executions in the traced
sub-window (profiler trace, ``XLA Modules`` line)."""


def read(rec):
    m = (rec.trace or {}).get("modules", {}).get("jit_serve_decode")
    return 1e3 * m[1] / m[0] if m and m[0] else None
