"""Device: the longest interval of the traced window in which no operation
ran on device 0, device trace (`trace_reduce.reduce`'s `longest_gaps`; the
harness prints the five longest with the host span that covers each)."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["longest_gaps"] or run["device"]["platform"] != "tpu":
        return None
    return 1e3 * tr["longest_gaps"][0][1]
