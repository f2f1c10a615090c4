"""Device idle milliseconds per Newton step while the host is inside the
program's host I/O spans: ``glu.prep`` (permute and scale), ``glu.h2d``,
``glu.d2h`` and ``glu.post`` (each instant counted once, by the innermost
span open over it; ``program_trace.reduce``'s ``span_idle_s``)."""

SPANS = ("glu.prep", "glu.h2d", "glu.d2h", "glu.post")


def read(ctx):
    p = ctx.get("program")
    if ctx.get("kind") != "newton" or not p or not ctx.get("steps"):
        return None
    idle = [p["span_idle_s"][s] for s in SPANS if s in p["span_idle_s"]]
    return 1e3 * sum(idle) / ctx["steps"] if idle else None
