"""Structured verification results.

Every check in the analysis subsystem reports through a
:class:`VerifyReport`: a flat list of :class:`Violation` records plus the
names of the checks that ran.  Reports are cheap append-only containers —
checks never raise on a finding; callers decide via
:meth:`VerifyReport.raise_if_violated` (the ``GLU(verify=...)`` knob does).

Violation codes are a closed vocabulary (see ``CODES``) so tests and CI can
assert on *which* invariant broke, not just that one did.
"""
from __future__ import annotations

import dataclasses

__all__ = ["Violation", "VerifyReport", "PlanVerificationError", "CODES"]

# code -> one-line meaning; the closed violation vocabulary
CODES = {
    # pattern / plan shape
    "PATTERN_MALFORMED": "CSC pattern arrays are not a valid sorted pattern",
    "DIAG_MISMATCH": "diag_idx does not point at the diagonal entries",
    "LEVELS_MALFORMED": "levels/order/level_ptr are mutually inconsistent",
    # schedule races (static, against the recomputed dependency DAG)
    "RACE_INTRA_LEVEL": "a dependency edge connects two same-level columns",
    "RACE_LEVEL_ORDER": "a dependency edge points level-backward",
    # normalisation arrays
    "NORM_OOB": "normalisation index outside [0, nnz)",
    "NORM_MISMATCH": "norm_idx/norm_diag disagree with the pattern's L entries",
    # update triples
    "TRIPLE_OOB": "update-triple index outside [0, nnz)",
    "TRIPLE_INCONSISTENT": "lidx/uidx/didx/dst_col rows+cols disagree",
    "TRIPLE_ORDER": "triples not sorted by (level, destination column)",
    "TRIPLE_SET_MISMATCH": "update-triple multiset differs from the pattern's",
    # A-value scatter map
    "SCATTER_OOB": "a_scatter slot outside [0, nnz)",
    "SCATTER_COLLISION": "a_scatter maps two A entries to one filled slot",
    "SCATTER_MISMATCH": "a_scatter target coordinates differ from A's",
    # triangular-solve schedules
    "TRISOLVE_FWD_RACE": "forward-solve entry reads a not-yet-final x",
    "TRISOLVE_FWD_SET": "forward-solve entry set differs from L's",
    "TRISOLVE_BWD_RACE": "backward-solve entry reads a not-yet-final x",
    "TRISOLVE_BWD_SET": "backward-solve entry/column set differs from U's",
    "TRISOLVE_DENSE_TAIL": "dense-tail trisolve step disagrees with the pattern",
    # reach closures
    "REACH_ADJ_MISMATCH": "plan DAG adjacency differs from the pattern's",
    "REACH_UNDER": "reach closure under-approximates (drops trisolve work)",
    "REACH_OVER": "reach closure over-approximates the true closure",
    # executed-schedule walk (post-bucketing groups)
    "EXEC_PAD_OOB": "group index outside [0, nnz] (nnz is the drop slot)",
    "EXEC_RACE": "an executed step writes an entry at/after a consuming read",
    "EXEC_SOURCE_ORDER": "an update fires before its source column is normal",
    "EXEC_NORM_COVERAGE": "executed normalisations differ from the plan's",
    "EXEC_UPDATE_COVERAGE": "executed update triples differ from the plan's",
    "EXEC_DENSE_TAIL": "dense-tail position map disagrees with the pattern",
    # jaxpr audit of the fused runners
    "AUDIT_CALLBACK": "fused program contains a host callback primitive",
    "AUDIT_DONATION": "buffer-donation contract of the runner not honoured",
    "AUDIT_DISPATCH": "whole-schedule execution is not a single dispatch",
}


@dataclasses.dataclass
class Violation:
    """One broken invariant.  ``context`` carries small structured details
    (offending indices, counts) for tests and CLI output."""

    code: str
    message: str
    context: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.code not in CODES:
            raise ValueError(f"unknown violation code {self.code!r}")

    def __str__(self) -> str:
        ctx = ""
        if self.context:
            parts = ", ".join(f"{k}={v}" for k, v in self.context.items())
            ctx = f" [{parts}]"
        return f"{self.code}: {self.message}{ctx}"


class PlanVerificationError(RuntimeError):
    """Raised by ``raise_if_violated`` / ``GLU(verify=...)`` on findings."""

    def __init__(self, report: "VerifyReport"):
        self.report = report
        lines = [str(v) for v in report.violations[:10]]
        extra = len(report.violations) - len(lines)
        if extra > 0:
            lines.append(f"... and {extra} more")
        super().__init__(
            "plan verification failed with "
            f"{len(report.violations)} violation(s):\n  " + "\n  ".join(lines))


@dataclasses.dataclass
class VerifyReport:
    """Outcome of one verification run: which checks ran, what they found."""

    checks: list = dataclasses.field(default_factory=list)
    violations: list = dataclasses.field(default_factory=list)

    # per-code cap on recorded examples; further findings only bump the
    # count in the first record's context (keeps reports bounded on
    # badly corrupted plans)
    MAX_PER_CODE = 8

    def ran(self, check: str) -> None:
        if check not in self.checks:
            self.checks.append(check)

    def add(self, code: str, message: str, **context) -> None:
        n = sum(1 for v in self.violations if v.code == code)
        if n >= self.MAX_PER_CODE:
            for v in self.violations:
                if v.code == code:
                    v.context["suppressed"] = v.context.get("suppressed", 0) + 1
                    break
            return
        self.violations.append(Violation(code, message, context))

    def merge(self, other: "VerifyReport") -> "VerifyReport":
        for c in other.checks:
            self.ran(c)
        self.violations.extend(other.violations)
        return self

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def codes(self) -> frozenset:
        return frozenset(v.code for v in self.violations)

    def raise_if_violated(self) -> "VerifyReport":
        if self.violations:
            raise PlanVerificationError(self)
        return self

    def summary(self) -> dict:
        """Small JSON-able digest — what ``solve_info['verify_report']``
        carries."""
        return {
            "ok": self.ok,
            "n_checks": len(self.checks),
            "n_violations": len(self.violations),
            "codes": sorted(self.codes),
        }

    def __str__(self) -> str:
        head = (f"VerifyReport: {len(self.checks)} checks, "
                f"{len(self.violations)} violation(s)")
        if self.ok:
            return head + " — OK"
        return head + "\n" + "\n".join(f"  {v}" for v in self.violations)
