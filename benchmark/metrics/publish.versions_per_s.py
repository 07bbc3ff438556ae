"""Weight publish: versions the publisher sent over the window."""


def read(run):
    return (run["close"]["published"] - run["open"]["published"]) / run["window_s"]
