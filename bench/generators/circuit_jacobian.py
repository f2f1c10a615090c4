"""Random circuit-Jacobian-like matrix: a mostly symmetric random coupling
pattern with ``asym`` value asymmetry, ``pattern_asym`` one-sided entries,
``n_rails`` high-degree nodes, and a diagonal of row-sum dominance plus a
0.5 leak."""
import numpy as np

from gen import Netlist, csc_from_coo


def make(n: int, avg_degree: float = 4.0, n_rails: int = 0,
         rail_fanout: int = 64, asym: float = 0.1, pattern_asym: float = 0.0,
         seed: int = 0):
    rng = np.random.default_rng(seed)
    m = int(n * avg_degree / 2)
    a = rng.integers(0, n, size=m)
    b = rng.integers(0, n, size=m)
    keep = a != b
    a, b = a[keep], b[keep]
    g = rng.uniform(0.1, 1.0, size=len(a))
    if pattern_asym > 0:
        one_sided = rng.uniform(size=len(a)) < pattern_asym
    else:
        one_sided = np.zeros(len(a), dtype=bool)
    two = ~one_sided
    rows = [a, b[two]]
    cols = [b, a[two]]
    vals = [-g, -g[two] * (1.0 - asym * rng.uniform(0, 1, size=two.sum()))]
    branch = [np.arange(len(a)), np.flatnonzero(two)]
    n_branches = len(a)
    for _ in range(n_rails):
        node = rng.integers(0, n)
        targets = rng.choice(n, size=min(rail_fanout, n - 1), replace=False)
        targets = targets[targets != node]
        gr = rng.uniform(0.1, 1.0, size=len(targets))
        rows.extend([np.full(len(targets), node), targets])
        cols.extend([targets, np.full(len(targets), node)])
        vals.extend([-gr, -gr])
        ids = n_branches + np.arange(len(targets))
        branch.extend([ids, ids])
        n_branches += len(targets)
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = np.concatenate(vals)
    net = Netlist(n, rows, cols, vals, np.concatenate(branch), n_branches,
                  0.5)
    diag = np.full(n, 0.5)
    np.add.at(diag, rows, np.abs(vals))
    rows = np.concatenate([rows, np.arange(n)])
    cols = np.concatenate([cols, np.arange(n)])
    vals = np.concatenate([vals, diag])
    return n, csc_from_coo(n, rows, cols, vals), net
