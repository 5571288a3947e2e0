"""Programs handed to the backend compiler inside the window
(``jax.monitoring`` backend-compile events; persistent-cache lookups count).
Should be 0."""


def read(run):
    return run.compiles_in_window
