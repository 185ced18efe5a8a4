"""Inner (Krylov) iterations per solve, mean over the run's solves, from
``SolveResult.inner_iterations``."""


def read(facts):
    solves = facts.get("solves")
    if not solves:
        return None
    return sum(i for _, i in solves) / len(solves)
