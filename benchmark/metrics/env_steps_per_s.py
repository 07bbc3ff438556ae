"""All env-steps of the optimizer steps taken in the window over the whole
window; the window is closed by a fence on its last step's result."""


def read(run):
    return run["env_steps"] / run["window_s"]
