"""Device step: the share of the step program's device time that carries
none of the configuration's declared scopes: operations the compiler made
itself (no `op_name`), operations outside every scope, and the time inside
the program in which no operation ran. Device trace by scope; nothing where
the scopes carry under nine tenths of the step (`scope_self_s` is None)."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["step_busy_s"] or not tr["scope_self_s"]:
        return None
    scoped = sum(v for k, v in tr["scope_self_s"].items() if k)
    return 100.0 * (1.0 - scoped / tr["step_busy_s"])
