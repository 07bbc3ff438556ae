"""The generator `republish`: threads in the harness's process republish
a pool of pre-built frames into the learner's in-process broker, holding
the queue between empty and its high-water mark, as `bench.py`
`_start_producers` does. Thread 0 reads the broker's weight slot as an
actor would and the pool is re-stamped with the newest version read, so
the publish leg is really consumed. It keeps its own clock of the time
spent waiting on the queue's depth: a generator that does not wait is
itself the bottleneck, and the cell then measures the generator.

A traffic mix names its generator (`"generator": {"module": "republish",
...}`) and gives every parameter below; none has a default here. What a
generator module owes the harness is `open_feed` and the `Feed` it
returns: everything between frames and the broker the learner reads is
the generator's own, so another module can put processes, a TCP server
or a fabric shard there.
"""

from __future__ import annotations

import threading
import time
from typing import List


def open_feed(spec: dict, cell_name: str, batch_rows: int, wire) -> "Feed":
    """`spec`: the traffic mix's `generator` entry; `wire`: its frame
    builder's module (for the header's version field)."""
    return Feed(spec, cell_name, batch_rows, wire)


class Feed:
    def __init__(self, spec: dict, cell_name: str, batch_rows: int, wire):
        from dotaclient_tpu.transport import memory as mem
        from dotaclient_tpu.transport.base import connect

        self.broker_url = spec["broker_url"].format(cell=cell_name)
        if not self.broker_url.startswith("mem://"):
            raise ValueError("this generator feeds an in-process mem:// broker")
        mem.reset(self.broker_url[len("mem://"):])
        self._maxlen = int(spec["queue_batches"]) * batch_rows
        self._connect = lambda: connect(self.broker_url, maxlen=self._maxlen)
        self._broker = self._connect()
        self._wire = wire
        self._high_water = int(spec["high_water_batches"]) * batch_rows
        self._pool_rows = int(spec["pool_batches"]) * batch_rows
        self._sleep = float(spec["poll_sleep_s"])
        self._burst = int(spec["burst"])
        self._poll_every = float(spec["weights_poll_s"])
        self._stamp = bool(spec["stamp_version"])
        n = int(spec["threads"])
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.version = 0  # newest weight version read back
        self.weights_read = 0
        self.published = [0] * n  # frames sent, per thread
        self._wait_s = [0.0] * n  # per-thread sums, read without a lock (gauges)

    def learner_broker(self):
        """The broker object the learner is built on."""
        return self._connect()

    def preload(self, frames: List[bytes]) -> None:
        """The run's frames from the seed: all of them reach the learner
        ahead of the traffic, and the first `pool_batches` batches of
        them are the pool that is republished."""
        if len(frames) >= self._maxlen:
            raise ValueError("the queue has to hold the preloaded frames and a batch more")
        for fr in frames:
            self._broker.publish_experience(fr)
        self._pool = list(frames[: self._pool_rows])
        self._pool_version = [0] * len(self._pool)

    def start(self) -> None:
        n = len(self.published)
        for i in range(n):
            t = threading.Thread(target=self._run, args=(i, n), daemon=True, name=f"bench-gen-{i}")
            self._threads.append(t)
            t.start()

    def waited_s(self) -> float:
        """Seconds the threads spent waiting on queue depth, averaged
        over the threads."""
        return sum(self._wait_s) / max(len(self._wait_s), 1)

    def _run(self, index: int, stride: int) -> None:
        broker, pool, pool_version, wire = self._broker, self._pool, self._pool_version, self._wire
        i = index
        n = len(pool)
        next_poll = 0.0
        while not self._stop.is_set():
            now = time.perf_counter()
            if index == 0 and self._stamp and now >= next_poll:
                # The newest weights, as an actor reads them: whether or
                # not the queue is full.
                next_poll = now + self._poll_every
                frame = broker.poll_weights()
                if frame is not None:
                    self.version = wire.weights_version(frame)
                    self.weights_read += 1
                    del frame
            room = self._high_water - broker.experience_depth()
            if room <= 0:
                time.sleep(self._sleep)
                self._wait_s[index] += time.perf_counter() - now
                continue
            v = self.version
            for _ in range(min(room, self._burst)):
                slot = i % n
                if self._stamp and pool_version[slot] != v:
                    pool[slot] = wire.stamp_version(pool[slot], v)
                    pool_version[slot] = v
                broker.publish_experience(pool[slot])
                i += stride
            self.published[index] = i // stride

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"generator threads did not stop: {alive}")
