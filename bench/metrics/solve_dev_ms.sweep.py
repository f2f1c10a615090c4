"""Device milliseconds per sweep call inside the solve side's programs,
``glu_trisolve``, ``glu_residual`` and ``glu_correct``
(``program_trace.reduce``'s ``program_device_s``), mean over the devices
used: the batched triangular solves and refinement sweeps."""

PROGRAMS = ("glu_trisolve", "glu_residual", "glu_correct")


def read(ctx):
    p = ctx.get("program")
    if ctx.get("kind") != "sweep" or not p or not ctx.get("steps"):
        return None
    dev = p["program_device_s"]
    s = [dev[k] for k in PROGRAMS if k in dev]
    return 1e3 * sum(s) / ctx["steps"] if s else None
