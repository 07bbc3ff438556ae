"""Set-up: the synchronous publish of version 0 on the loop thread at the
start of `Learner.run` (the program's span `setup.publish0`, cumulative
seconds as the window's first metrics window has them: all of it lies before
the window)."""

KEY = "span_setup_publish0_s_total"


def read(run):
    for s in run["syncs"]:
        if KEY in s[2]:
            return s[2][KEY]
    return None
