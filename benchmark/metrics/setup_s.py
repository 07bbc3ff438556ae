"""Process start to window open: imports, frames and weights from the seed,
compile or cache load, the check's first steps, warm-up."""


def read(run):
    return run["setup_s"]
