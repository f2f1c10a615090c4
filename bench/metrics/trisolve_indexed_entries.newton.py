"""Padded gather/scatter entries one triangular solve walks, forward and
backward (``solve_info["trisolve_indexed_entries"]``), mean over the traced
Newton steps."""


def read(ctx):
    infos = ctx.get("solve_info")
    if ctx.get("kind") != "newton" or not infos:
        return None
    v = [i.get("trisolve_indexed_entries") for i in infos]
    if any(x is None for x in v):
        return None
    return sum(v) / len(v)
