"""Backend-compile milliseconds per candidate executed in the window: the
seconds of JAX's own backend-compile events in the window, over the
candidates the search executed and scored there."""


def read(rec):
    c = rec.counters
    if not c.get("evals"):
        return None
    return 1e3 * c["backend_compile_s"] / c["evals"]
