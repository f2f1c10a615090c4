"""Device idle milliseconds per Newton step while the host is inside the
refinement driver's spans: ``glu.refine`` and ``glu.sync`` (each read of
the backward error back to the host), by ``program_trace.reduce``'s
``span_idle_s``."""

SPANS = ("glu.refine", "glu.sync")


def read(ctx):
    p = ctx.get("program")
    if ctx.get("kind") != "newton" or not p or not ctx.get("steps"):
        return None
    idle = [p["span_idle_s"][s] for s in SPANS if s in p["span_idle_s"]]
    return 1e3 * sum(idle) / ctx["steps"] if idle else None
