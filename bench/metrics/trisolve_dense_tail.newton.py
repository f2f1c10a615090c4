"""Columns of the trailing dense block that the triangular solve runs as
dense substitution (``solve_info["trisolve_dense_tail"]``; 0 where the
solve walks every level by index), mean over the traced Newton steps."""


def read(ctx):
    infos = ctx.get("solve_info")
    if ctx.get("kind") != "newton" or not infos:
        return None
    v = [i.get("trisolve_dense_tail") for i in infos]
    if any(x is None for x in v):
        return None
    return sum(v) / len(v)
