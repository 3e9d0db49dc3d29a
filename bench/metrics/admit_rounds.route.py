"""Admission rounds per day-plan call: the program's own ``admit_rounds``
counter of each call (``PlacementState``), read once the window has
closed, averaged over the window's calls."""


def read(o):
    if not o.admit_rounds:
        return None
    return sum(o.admit_rounds) / len(o.admit_rounds)
