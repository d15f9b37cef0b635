"""Process start to window start: bank, traffic, warm-up, compiles."""


def read(run):
    return run.setup_s
