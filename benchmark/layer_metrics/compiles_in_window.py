"""Compile plane: XLA compilations, and loads from the persistent cache,
that happened inside the window.  Expected 0: every shape is warmed up in
set-up."""


def read(run):
    return float(run["compiles_in_window"])
