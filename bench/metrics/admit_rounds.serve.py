"""Admission rounds per serve draft: the program's own ``admit_rounds``
counter, one value per draft (``QueueServeResult.admit_rounds``), averaged
over the window's drafts."""


def read(o):
    if not o.admit_rounds:
        return None
    return sum(o.admit_rounds) / len(o.admit_rounds)
