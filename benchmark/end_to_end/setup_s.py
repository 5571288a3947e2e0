"""Process start to the first request of the window: data from the seed,
store, planner, staging, warm-up and what compiles."""


def read(run):
    return run.setup_s
