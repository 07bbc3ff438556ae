"""Set-up: all of `Learner.__init__`: building the step, initial parameters,
staging, publisher, restore (the program's span `setup.learner_init`,
cumulative seconds as the window's first metrics window has them: all of it
lies before the window)."""

KEY = "span_setup_learner_init_s_total"


def read(run):
    for s in run["syncs"]:
        if KEY in s[2]:
            return s[2][KEY]
    return None
