"""Process start to the window's start: load, encode, warm-up, compiles."""


def read(run):
    return run.setup_s
