"""Blocking host transfers per solve: the program's host-transfer counter
over each ``repro.solve`` span in the traced window (the span's
``host_transfers`` stat), the mean over those solves."""

from bench import scopes


def read(facts):
    p = scopes.from_facts(facts)
    if p is None:
        return None
    lo, hi = scopes.window(p, facts)
    counts = [st[scopes.TRANSFERS_STAT]
              for _, st in scopes.program_spans(p, (scopes.SOLVE_SPAN,),
                                                lo, hi)
              if scopes.TRANSFERS_STAT in st]
    return sum(counts) / len(counts) if counts else None
