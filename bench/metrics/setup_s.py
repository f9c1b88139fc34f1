"""Process start to the window's opening: JAX start-up, data generation,
plan, compile or cache load, warm-up and fill."""


def read(run):
    return run.setup_s
