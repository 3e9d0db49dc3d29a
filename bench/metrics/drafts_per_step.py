"""Drafts per serve step: the loop's own ``QueueStep.n_batches``,
summed over the window's steps and divided by their number."""


def read(o):
    if not o.drafts_per_step:
        return None
    return sum(o.drafts_per_step) / len(o.drafts_per_step)
