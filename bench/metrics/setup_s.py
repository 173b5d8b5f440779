"""Process start to the window's opening: weights, engine, warm-up."""


def read(run):
    return run.setup_s
