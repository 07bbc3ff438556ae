"""Set-up: seconds the process spent making its programs before the window:
trace, lower and compile or load from the cache, of every program from the
learner's construction on (the step and the weight flatten are nearly all of
it; the small programs inside `Learner.__init__` are in
`setup.learner_init_s` as well). The program's `compile_s_total` as the
window's first metrics window has it: nothing compiles inside the window
(`step.compiles_in_window`)."""

KEY = "compile_s_total"


def read(run):
    for s in run["syncs"]:
        if KEY in s[2]:
            return s[2][KEY]
    return None
