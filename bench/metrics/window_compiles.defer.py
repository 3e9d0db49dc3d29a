"""Programs compiled, or loaded from the persistent compile cache, inside
the deferral day plan's measured window, from jax's monitoring events, as
``window_compiles`` reads them. Should be 0."""

from pathlib import Path

from harness import cells

read = cells.module(Path(__file__).parent / "window_compiles.py").read
