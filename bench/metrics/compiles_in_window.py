"""Executables built or loaded inside the window: ``jax.monitoring``
backend-compile events between the window's start and its end (0 when
set-up warmed every shape)."""


def read(run):
    return float(run.compiles_in_window)
