"""Traffic generator (benchmark): share of the window its threads spent
waiting on the queue's depth, by the generator's own clock."""


def read(run):
    waited = run["close"]["gen_wait_s"] - run["open"]["gen_wait_s"]
    return 100.0 * waited / run["window_s"]
