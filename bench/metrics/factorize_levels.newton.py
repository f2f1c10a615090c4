"""Sparse levels one factorize walks before the dense block
(``solve_info["factorize_levels"]``), mean over the traced Newton steps."""


def read(ctx):
    infos = ctx.get("solve_info")
    if ctx.get("kind") != "newton" or not infos:
        return None
    v = [i.get("factorize_levels") for i in infos]
    if any(x is None for x in v):
        return None
    return sum(v) / len(v)
