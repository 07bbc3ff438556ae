"""Weight publish: submits superseded before they were sent, over the
submits of the window (one every `publish_every` steps)."""


def read(run):
    every = run["publish_every"]
    submits = run["close"]["steps"] // every - run["open"]["steps"] // every
    if submits <= 0:
        return None
    return 100.0 * (run["close"]["coalesced"] - run["open"]["coalesced"]) / submits
