"""Device programs launched per Newton step: module executions on the
device that start inside a ``bench.step`` span (``program_trace.reduce``'s
``program_count``), mean over the devices used."""


def read(ctx):
    p = ctx.get("program")
    if ctx.get("kind") != "newton" or not p or not ctx.get("steps"):
        return None
    return p["program_count"] / ctx["steps"] or None
