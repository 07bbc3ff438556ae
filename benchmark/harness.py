"""One run of one cell: build the learner from the configuration's file,
feed it from the traffic generator, drive it by `Learner.run`, open and
close the measured window around its train-step calls, then compare what
it produced with the plain reference.

From the program this takes the system under test (`Learner`, its
`LearnerConfig`) and its counters; the traffic mix's generator and frame
builder take the broker and the actor-side frame encoder. Everything
that measures or judges is the benchmark's own. The generator, the frame
builder and the plain reference are modules that the traffic mix's and
the configuration's files name, found under the benchmark's directories
as a metric's reader is.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import time
from typing import List, Optional

import numpy as np

from benchmark import cells, check, frames, trace_reduce, weights
from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms

CACHE_DIR = os.path.join(cells.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(cells.ROOT, ".bench_trace")


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _fields(cls, data: dict, constants: dict, section: str) -> dict:
    """`data`'s keys that are fields of the program's dataclass `cls`. A
    key that is not one has to be among the configuration's
    `program_constants` (what the program fixes in code: stated for the
    reference, which reads it), so that a misspelt field is refused."""
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = [k for k in data if k not in names and f"{section}.{k}" not in constants]
    if unknown:
        raise ValueError(f"{section}: {unknown} are neither fields of {cls.__name__} "
                         f"nor listed under program_constants")
    return {k: v for k, v in data.items() if k in names}


def _override(cfg, path: str, value):
    """`cfg` with the field at the dotted `path` replaced."""
    head, _, rest = path.partition(".")
    if not hasattr(cfg, head):
        raise ValueError(f"{type(cfg).__name__} has no field {head!r}")
    return dataclasses.replace(
        cfg, **{head: _override(getattr(cfg, head), rest, value) if rest else value})


def learner_config(cell: dict, seed: int, broker_url: str):
    """The program's `LearnerConfig` for this cell, all from data: the
    configuration file's `policy`, `ppo` and `learner` sections go to the
    fields of those names (`rows_per_chip` times the cell's chips is the
    batch), then the configuration's and the traffic mix's
    `learner_overrides` (dotted paths into `LearnerConfig`) are applied;
    the program's defaults otherwise."""
    from dotaclient_tpu.config import LearnerConfig, PolicyConfig, PPOConfig

    c = cell["config_data"]
    constants = c["program_constants"]
    top = dict(c["learner"])
    cfg = LearnerConfig(
        batch_size=int(top.pop("rows_per_chip")) * int(cell["chips"]),
        policy=PolicyConfig(**_fields(PolicyConfig, c["policy"], constants, "policy")),
        ppo=PPOConfig(**_fields(PPOConfig, c["ppo"], constants, "ppo")),
        broker_url=broker_url,
        seed=int(seed) % (2**31 - 1),
        **_fields(LearnerConfig, top, constants, "learner"),
    )
    for source in (c, cell["traffic_data"]):
        for path, value in source["learner_overrides"].items():
            cfg = _override(cfg, path, value)
    return cfg


def check_features(config: dict) -> None:
    """The configuration's file states the wire's feature sizes; the
    program has to agree, or every frame would be refused."""
    from dotaclient_tpu.env import featurizer as F

    f = config["features"]
    have = {
        "max_units": F.MAX_UNITS, "unit_features": F.UNIT_FEATURES,
        "hero_features": F.HERO_FEATURES, "global_features": F.GLOBAL_FEATURES,
        "n_action_types": F.N_ACTION_TYPES,
    }
    if {k: int(f[k]) for k in have} != have:
        raise ValueError(f"configuration's features {f} are not the program's {have}")


class StepProbe:
    """Stands in the learner's `train_step` attribute: every optimizer
    step of `Learner.run` goes through `__call__` on the loop thread. It
    calls the compiled step and nothing else on the path; around the
    calls it keeps the first steps' results for the check, decides when
    the window opens and closes, and fences both ends on a step's result.
    """

    WARMUP, WINDOW, DONE = "warmup", "window", "done"

    def __init__(self, learner, feed, plan: dict, shapes: dict, seed: int, check_steps: int,
                 t_start: float):
        import jax

        self._jax = jax
        self.t_start = t_start
        self.learner = learner
        self.feed = feed
        self.inner = learner.train_step
        self.plan = plan
        self.phase = self.WARMUP
        self.calls = 0
        self.last = None  # newest step's loss, still on the device
        self.open: Optional[dict] = None
        self.close: Optional[dict] = None
        self.trace_open: Optional[dict] = None
        self.tracing = False
        self.check_steps = check_steps
        self.check_losses: List = []
        self.check_mu = None
        self.check_change = None
        # Both take the seed's key and make what they compare with inside
        # the program (the initial parameters, the sign vectors), so the
        # harness keeps nothing parameter-sized on the device.
        make = weights.maker(shapes)
        self._first_gradient = jax.jit(first_gradient)
        self._change = jax.jit(lambda params, key: leaf_diff_norms(params, make(key)))
        self._keys = (check.sketch_key(seed), weights.seed_key(seed))
        # (host time, learner version) at each of the program's metric
        # syncs: the only times at which a step is known to be complete.
        self.syncs: List[tuple] = []
        self.marks: List[tuple] = []  # per step: (t, published, coalesced, gen wait)
        log = learner.metrics.log

        def logged(step, scalars):
            self.syncs.append((time.perf_counter(), int(step), dict(scalars)))
            return log(step, scalars)

        learner.metrics.log = logged

    def counters(self) -> dict:
        lr = self.learner
        st = lr.staging.stats()
        return {
            "t": time.perf_counter(),
            "steps": self.calls,
            "env_steps": int(lr.env_steps_done),
            "published": int(lr.publisher.published),
            "coalesced": int(lr.publisher.coalesced),
            "consumed": int(st["consumed"]),
            "dropped_stale": int(st["dropped_stale"]),
            "dropped_bad": int(st["dropped_bad"]),
            "quarantined": int(st["quarantined"]),
            "rows_packed": int(st["rows_packed"]),
            "gen_wait_s": self.feed.waited_s(),
            "gen_published": int(sum(self.feed.published)),
            "weights_read": int(self.feed.weights_read),
        }

    def _fence(self) -> None:
        if self.last is not None:
            self._jax.block_until_ready(self.last)

    def _warm(self) -> bool:
        w = self.plan["warmup"]
        return (
            self.calls >= max(int(w["min_steps"]), self.check_steps + 1)
            and self.learner.publisher.published >= int(w["min_publishes"])
        )

    def __call__(self, state, batch):
        now = time.perf_counter()
        if self.phase == self.WARMUP and self._warm():
            self._fence()
            self.open = self.counters()
            self.phase = self.WINDOW
        elif self.phase == self.WINDOW:
            if (
                self.plan["trace"]
                and not self.tracing
                and now >= self.open["t"] + self.plan["seconds"] - self.plan["trace_seconds"]
            ):
                self._fence()
                self._start_trace()
                self.trace_open = self.counters()
            if now >= self.open["t"] + self.plan["seconds"]:
                self._fence()
                self.close = self.counters()
                if self.tracing:
                    self._span.__exit__(None, None, None)
                    self._jax.profiler.stop_trace()
                    self.tracing = False
                self.phase = self.DONE
                self.learner.abort()
        out = self.inner(state, batch)
        self.calls += 1
        self.last = out[1]["loss"]
        if self.calls in (1, self.check_steps):
            say(f"setup: step {self.calls} dispatched {time.time() - self.t_start:.2f} s after start")
        if self.calls <= self.check_steps:
            self.check_losses.append(out[1]["loss"])
            if self.calls == 1:
                self.check_mu = self._first_gradient(_adam_mu(out[0].opt_state), self._keys[0])
            if self.calls == self.check_steps:
                self.check_change = self._change(out[0].params, self._keys[1])
        if self.phase != self.DONE:
            pub = self.learner.publisher
            self.marks.append(
                (now, int(pub.published), int(pub.coalesced), self.feed.waited_s())
            )
        return out

    def _start_trace(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = self._jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        opts.host_tracer_level = 2
        self._jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.tracing = True
        # The traced window, as a span in the trace itself: from here to
        # the closing fence. Starting and stopping the profiler idles the
        # device and is no part of it.
        self._span = self._jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def check_readings(self, adam_b1: float) -> dict:
        """The program's side of the comparison, as host numbers."""
        get = self._jax.device_get
        # Adam's first moment after one step is (1 - b1) times the
        # gradient as the optimizer got it.
        norms, sketches = (
            {k: v / (1.0 - adam_b1) for k, v in flat_numbers(t).items()}
            for t in get(self.check_mu)
        )
        return {
            "losses": [float(x) for x in get(self.check_losses)],
            "grad": norms,
            "grad_sketch": sketches,
            "change": flat_numbers(get(self.check_change)),
        }


def _adam_mu(opt_state):
    import jax

    found = [
        s for s in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise ValueError("expected one Adam state in the learner's optimizer state")
    return found[0].mu


def slices(probe: StepProbe, env_steps_per_step: float, slice_s: float) -> List[dict]:
    """The window cut into slices of `slice_s` seconds: the rate of each
    from the program's sync times (a step is known complete only there),
    and the publishes, coalesced submits and generator wait of each from
    the per-step marks."""
    t0, t1 = probe.open["t"], probe.close["t"]
    pts = [(t0, probe.open["steps"])] + [
        (t, v) for t, v, _ in probe.syncs if t0 < t < t1
    ] + [(t1, probe.close["steps"])]
    ts = np.array([p[0] for p in pts])
    vs = np.array([p[1] for p in pts], dtype=float)
    marks = np.array(probe.marks) if probe.marks else np.zeros((0, 4))
    out = []
    a = t0
    while a < t1 - 1e-9:
        b = min(a + slice_s, t1)
        steps = float(np.interp(b, ts, vs) - np.interp(a, ts, vs))
        row = {"from_s": round(a - t0, 3), "to_s": round(b - t0, 3),
               "env_steps_per_s": steps * env_steps_per_step / (b - a)}
        inside = marks[(marks[:, 0] >= a) & (marks[:, 0] < b)] if len(marks) else marks
        if len(inside) >= 2:
            row["published"] = int(inside[-1, 1] - inside[0, 1])
            row["coalesced"] = int(inside[-1, 2] - inside[0, 2])
            row["gen_throttled_pct"] = 100.0 * (inside[-1, 3] - inside[0, 3]) / max(
                inside[-1, 0] - inside[0, 0], 1e-9
            )
        out.append(row)
        a = b
    return out


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices, root: str = cells.ROOT, break_step=None) -> dict:
    """Everything between the look for a chip (the caller's) and the
    result line (the caller's): set-up, window, check, metrics.
    `break_step`, for the benchmark's own tests: wraps the learner's
    compiled step in a planted fault, underneath everything here."""
    import jax

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.runtime.learner import Learner

    say(f"setup: imports done {time.time() - t_start:.2f} s after start")
    cell = cells.load_cell(bench, workload, root)
    config, traffic = cell["config_data"], cell["traffic_data"]
    check_features(config)
    chips = int(cell["chips"])
    devices = list(devices)[:chips]
    B = int(config["learner"]["rows_per_chip"]) * chips
    check_steps = int(config["check"]["reference_steps"])
    reference = cells.load_module(bench, "references", config["reference"], root)
    wire = cells.load_module(bench, "wire", traffic["frames"]["module"], root)
    generator = cells.load_module(bench, "generators", traffic["generator"]["module"], root)

    # Rows and frames from the seed: the check's batches, all rows
    # distinct; the generator takes its pool from among them.
    t = time.perf_counter()
    rows = frames.make_rows(config, traffic["rows"], check_steps * B, seed)
    sent = wire.serialize_rows(rows, traffic["frames"])
    say(f"setup: {len(sent)} frames of {len(sent[0])} B in {time.perf_counter() - t:.2f} s")

    feed = generator.open_feed(traffic["generator"], cell["name"], B, wire)
    cfg = learner_config(cell, seed, feed.broker_url)
    mesh = mesh_lib.make_mesh(cfg.mesh_shape, devices=devices)
    t = time.perf_counter()
    learner = Learner(cfg, feed.learner_broker(), mesh=mesh)
    say(f"setup: Learner built in {time.perf_counter() - t:.2f} s")

    # The benchmark's weights, in the place of the learner's own.
    t = time.perf_counter()
    shapes = reference.param_shapes(config)
    learner.state = learner.state._replace(
        params=weights.make_params(shapes, seed, out_shardings=learner.state_shardings.params))
    jax.block_until_ready(learner.state.params)
    say(f"setup: weights from the seed in {time.perf_counter() - t:.2f} s")

    feed.preload(sent)
    del sent
    plan = {
        "warmup": traffic["warmup"], "seconds": float(seconds), "trace": bool(trace),
        "trace_seconds": min(float(traffic["trace_seconds"]), float(seconds)),
    }
    if break_step is not None:
        learner.train_step = break_step(learner.train_step)
    probe = StepProbe(learner, feed, plan, shapes, seed, check_steps, t_start)
    learner.train_step = probe
    feed.start()
    try:
        learner.run(max_seconds=float(seconds) + 900.0, batch_timeout=30.0, max_idle=4)
    finally:
        if probe.tracing:
            jax.profiler.stop_trace()
        feed.stop()
    if probe.close is None:
        raise RuntimeError("the learner's run ended before the window closed")
    o, c = probe.open, probe.close
    window_s = c["t"] - o["t"]
    setup_s = (o["t"] - time.perf_counter()) + (time.time() - t_start)
    peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0)) if d.memory_stats() else 0
               for d in devices)
    steps = c["steps"] - o["steps"]
    env_steps = c["env_steps"] - o["env_steps"]
    say(f"window: {steps} steps, {env_steps} env-steps in {window_s:.3f} s; "
        f"set-up {setup_s:.2f} s; warm-up ended at step {o['steps']}, "
        f"{o['published']} publishes; peak {peak / 1e9:.3f} GB")
    for row in slices(probe, env_steps / max(steps, 1), float(traffic["slice_seconds"])):
        say("slice " + json.dumps(row))

    # The program's side of the check, then its state goes.
    got = probe.check_readings(float(config["ppo"]["adam_b1"]))
    learner.close()
    learner.state = None
    learner.train_step = None
    probe.inner = None
    probe.last = None
    gc.collect()

    t = time.perf_counter()
    numbers, where = check.compare_with_reference(bench, root, config, seed, rows, got, mesh)
    say(f"check: reference followed {check_steps} steps in {time.perf_counter() - t:.2f} s")
    correct = all(v <= lim for v, lim in numbers.values())

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    run = {
        "cell": cell, "config": config, "traffic": traffic, "chips": chips,
        "open": o, "close": c, "trace_open": probe.trace_open, "window_s": window_s,
        "steps": steps, "env_steps": env_steps, "setup_s": setup_s,
        "syncs": [s for s in probe.syncs if o["t"] < s[0] <= c["t"] + 1.0],
        "device": device, "peak_bytes": peak, "publish_every": cfg.publish_every,
        "rows_per_step": B, "trace": None,
        "step_flops": reference.train_step_flops(config, B),
        # one chip's share of a step, by layer scope, where the reference counts it
        "scope_costs": reference.scope_costs(config, B // chips)
        if hasattr(reference, "scope_costs") else {},
    }
    result = {
        "correct": bool(correct),
        "attempted": int(c["consumed"] - o["consumed"]),
        "failed": int(
            (c["dropped_stale"] - o["dropped_stale"]) + (c["dropped_bad"] - o["dropped_bad"])
        ),
        "metrics": {},
        "device": device,
    }
    if trace:
        t = time.perf_counter()
        found = sorted(glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError("the profiler left no trace")
        try:
            events = trace_reduce.read_xplane(found[-1])
            size = os.path.getsize(found[-1])
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run["trace"] = trace_reduce.reduce(
            events, chips=chips, scopes=cells.load_scopes(bench, cell["config"], root))
        say(f"trace: {size / 1e6:.1f} MB read and reduced in {time.perf_counter() - t:.2f} s")
        for at, gap, under in run["trace"]["longest_gaps"]:
            say(f"trace: device idle {1e3 * gap:.3f} ms at +{at:.3f} s under {under}")
        by_scope = run["trace"]["scope_self_s"]  # None: no scopes declared, or they carry too little
        say("trace: step by scope, ms: " + json.dumps(by_scope and {
            k: round(1e3 * v / run["trace"]["step_count"], 4) for k, v in by_scope.items()}))
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
        for m in cells.metrics_for(bench, workload, "per_layer"):
            value = cells.load_reader(bench, m["name"], root)(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
            else:
                say(f"note {m['name']}: its reader found nothing to read in this run")
    else:
        for m in cells.metrics_for(bench, workload, "end_to_end"):
            value = cells.load_reader(bench, m["name"], root)(run)
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    for k, note in where.items():
        if k not in numbers:
            say(f"note {k}: {note} (not compared)")
    for k, (v, lim) in numbers.items():
        say(f"check {k}: {v:.6g} (limit {lim:g}) {'ok' if v <= lim else 'NOT CORRECT'}"
            + (f" [{where[k].split(' ', 1)[1]}]" if "worst leaf" in where.get(k, "") else ""))
    return result
