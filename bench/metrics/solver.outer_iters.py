"""Outer (policy-improvement) iterations per solve, mean over the run's
solves, from ``SolveResult.outer_iterations``."""


def read(facts):
    solves = facts.get("solves")
    if not solves:
        return None
    return sum(o for o, _ in solves) / len(solves)
