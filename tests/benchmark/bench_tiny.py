"""A temporary copy of the benchmark with one more configuration, two more
traffic mixes (one of them with a generator module of its own), a metric
and three cells, all tiny: made by adding files and entries, editing no
file that is there."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The repository's root on the path, so that `benchmark` imports as it does
# from `benchmark/run.py`. (No conftest.py here: other test files import the
# suite's own `conftest` by that name.)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_READER = '''"""Steps in the window (a metric a later PR might add)."""


def read(run):
    return float(run["steps"]) if run["steps"] else None
'''


TINY_GENERATOR = '''"""Traffic code a later PR might add: one thread sends whole batches for
`on_s` seconds, then nothing for `off_s` seconds."""

import threading
import time


def open_feed(spec, cell_name, batch_rows, wire):
    return Feed(spec, cell_name, batch_rows, wire)


class Feed:
    def __init__(self, spec, cell_name, batch_rows, wire):
        from dotaclient_tpu.transport import memory as mem
        from dotaclient_tpu.transport.base import connect

        self.broker_url = "mem://tiny-bursty-" + cell_name
        mem.reset(self.broker_url[len("mem://"):])
        self._maxlen = int(spec["queue_batches"]) * batch_rows
        self._broker = self.learner_broker()
        self._spec, self._batch_rows = spec, batch_rows
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._wait_s = 0.0
        self.published = [0]
        self.weights_read = 0
        self.bursts = 0

    def learner_broker(self):
        from dotaclient_tpu.transport.base import connect

        return connect(self.broker_url, maxlen=self._maxlen)

    def preload(self, frames):
        for fr in frames:
            self._broker.publish_experience(fr)
        self._pool = list(frames[: self._batch_rows])

    def start(self):
        self._thread.start()

    def waited_s(self):
        return self._wait_s

    def _run(self):
        on_s, off_s = float(self._spec["on_s"]), float(self._spec["off_s"])
        while not self._stop.is_set():
            self.bursts += 1
            until = time.perf_counter() + on_s
            while time.perf_counter() < until and not self._stop.is_set():
                if self._broker.experience_depth() + self._batch_rows > self._maxlen:
                    t = time.perf_counter()
                    time.sleep(0.002)
                    self._wait_s += time.perf_counter() - t
                    continue
                for fr in self._pool:
                    self._broker.publish_experience(fr)
                self.published[0] += len(self._pool)
            self._stop.wait(off_s)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)
'''


def snapshot(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def make_tiny_root(dst: str, dtype: str = "float32") -> str:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(dst, exist_ok=True)
    for d in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, d), os.path.join(dst, d),
                        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    before = snapshot(dst)

    with open(os.path.join(dst, "benchmark", "configs", "lstm4096-openai-five.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "lstm64-tiny"
    cfg["policy"].update(lstm_hidden=64, unit_embed_dim=32, mlp_hidden=32, dtype=dtype)
    cfg["learner"].update(rows_per_chip=64, publish_every=4)
    cfg["ppo"].update(max_staleness=12)
    cfg["learner_overrides"] = {"staging.transfer_depth": 3}
    # The tiny configuration states float32 compute, so its control is
    # bfloat16. On the CPU the program reads under 1e-6 on every number
    # and the bfloat16 control 2e-5 to 2e-4 on the losses and 2e-3 to
    # 1.5e-2 on the norms (seeds 21 to 24): the limits stand between.
    cfg["check"]["limits"] = {"loss_gap_1": 1e-5, "loss_gap_2": 1e-5, "loss_gap_3": 1e-5,
                               "grad_norm_gap": 5e-4,
                               "grad_error": 1e-3, "update_norm_gap": 5e-4}
    with open(os.path.join(dst, "benchmark", "configs", "lstm64-tiny.json"), "w") as f:
        json.dump(cfg, f)
    cfg["name"] = "lstm64-tiny-dp"
    with open(os.path.join(dst, "benchmark", "configs", "lstm64-tiny-dp.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(dst, "benchmark", "traffic", "wire-saturated.json")) as f:
        traffic = json.load(f)
    traffic["name"] = "wire-tiny"
    traffic["warmup"] = {"min_steps": 6, "min_publishes": 1}
    traffic["slice_seconds"] = 1
    with open(os.path.join(dst, "benchmark", "traffic", "wire-tiny.json"), "w") as f:
        json.dump(traffic, f)
    # A mix whose traffic code is its own: a generator module found by name.
    bursty = json.loads(json.dumps(traffic))
    bursty["name"] = "wire-tiny-bursty"
    bursty["generator"] = {"module": "tiny_bursty", "queue_batches": 6, "on_s": 0.3, "off_s": 0.1}
    # This generator does not stamp versions, so its mix turns the staleness drop off.
    bursty["learner_overrides"] = {"metrics_every": 5, "ppo.max_staleness": 1000000}
    with open(os.path.join(dst, "benchmark", "traffic", "wire-tiny-bursty.json"), "w") as f:
        json.dump(bursty, f)
    with open(os.path.join(dst, "benchmark", "generators", "tiny_bursty.py"), "w") as f:
        f.write(TINY_GENERATOR)
    with open(os.path.join(dst, "benchmark", "metrics", "tiny.steps.py"), "w") as f:
        f.write(TINY_READER)

    bench["configs"].append({"name": "lstm64-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/lstm64-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny", "config": "lstm64-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    bench["workloads"].append({"name": "tiny-bursty", "config": "lstm64-tiny",
                               "traffic": "wire-tiny-bursty", "chips": 1, "why": "test"})
    bench["configs"].append({"name": "lstm64-tiny-dp", "source": "test", "reduced": [],
                             "file": "benchmark/configs/lstm64-tiny-dp.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-dp4", "config": "lstm64-tiny-dp", "traffic": "wire-tiny",
                               "chips": 4, "why": "test"})
    bench["per_layer"].append({"name": "tiny.steps", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "learner loop",
                               "moves": "env_steps_per_s",
                               "workloads": ["tiny", "tiny-bursty", "tiny-dp4"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = snapshot(dst)
    edited = [k for k in before if after.get(k) != before[k]]
    assert not edited, f"adding a cell edited files that were there: {edited}"
    return dst
