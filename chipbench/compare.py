"""The comparison that decides ``correct``: worst relative error per field
against the plain reference, and Algorithm-1 winners that differ.

``ErrorTable`` follows the one the on-chip smoke test keeps, with two
changes: a value that is not finite reads as an infinite error instead of
stopping the run, and winners are compared by their organization's
fields, so the program's and the reference's classes need not be the
same.
"""

from __future__ import annotations

import math


def rel_err(got: float, want: float) -> float:
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / abs(want) if want else abs(got)


def org_key(org) -> tuple:
    return (int(org.banks), int(org.rows), int(org.cols), str(org.access))


class ErrorTable:
    """Worst relative error per field, and Algorithm-1 winner mismatches."""

    def __init__(self):
        self.worst: dict[str, float] = {}
        self.compared = 0
        self.winners = 0
        self.winners_differing: list[str] = []

    def add(self, field: str, got: float, want: float) -> None:
        self.compared += 1
        self.worst[field] = max(self.worst.get(field, 0.0),
                                rel_err(float(got), float(want)))

    def winner(self, label: str, got_org, want_org) -> None:
        self.winners += 1
        if org_key(got_org) != org_key(want_org):
            self.winners_differing.append(
                f"{label}: device {org_key(got_org)} vs reference "
                f"{org_key(want_org)}")

    def max_err(self) -> float:
        """The worst relative error over every field; infinite when
        nothing was compared, so an empty check cannot pass."""
        return max(self.worst.values()) if self.worst else math.inf

    def summary(self) -> dict:
        return {"compared": self.compared, "worst": dict(self.worst),
                "winners": self.winners,
                "winners_differing": self.winners_differing[:20]}
