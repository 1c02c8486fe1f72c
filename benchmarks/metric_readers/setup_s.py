"""Process start to the first measured instant, compilation included."""


def read(run):
    return run.setup_s
