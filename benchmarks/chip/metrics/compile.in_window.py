"""Backend compiles (``jax.monitoring``) between the first dispatch and
the window's end: a cell whose shapes were all warmed up reads 0."""


def read(run):
    return run.compiles_in_window
