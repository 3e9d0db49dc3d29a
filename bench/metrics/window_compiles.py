"""Programs compiled, or loaded from the persistent compile cache,
inside the measured window, from jax's monitoring events. Should be 0."""


def read(o):
    return o.programs_in_window
