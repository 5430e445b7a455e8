"""Programs compiled or loaded inside the window (engine tick,
``serving/engine.py``): JAX's backend-compile events between the window's
open and close.  Every shape the cell reaches is warmed in set-up, so
this reads 0; anything else is a stall inside the window."""


def read(run):
    return run.compiles_in_window
