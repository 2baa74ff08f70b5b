"""XLA compilations and compilation-cache loads that JAX reported between
the window's start and its close. The window should hold none."""


def read(run):
    return float(run.compiles_in_window)
