"""Backend compiles during the timed window (``jax.monitoring``'s
backend-compile event); a warm run should read 0."""


def read(run):
    return run.compiles_in_window
