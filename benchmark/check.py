"""The comparison that decides `correct`: what the timed path produced in
its first steps (the same learner object, the window's own call and feed,
at the timed sizes) against the plain reference following the same steps
from the same seed.

Numbers compared, each with a limit of its own from the configuration's
file (PERF.md gives the readings each limit was set from):

- `loss_gap_<k>`: |program's loss - reference's| at step k, for the steps
  whose loss the configuration's file gives a limit (the later steps'
  losses are printed, not compared: PERF.md gives the readings and why);
- `grad_norm_gap`: the first gradient as the optimizer gets it (after the
  clip; read from Adam's first moment after one step), worst leaf: the
  gap between the two norms of the leaf over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- `grad_norm_gap_median`: the same measure's median over the leaves;
- `grad_error`: the norm of the difference between that first gradient
  and the reference's, over the reference's norm, of the whole parameter
  vector; estimated from both sides' sketches of every leaf on the same
  sign vectors (`tree.sketch`), so neither side keeps the other's
  tensors. First order in a rounding error where the gap of two norms is
  second order: the program and a lower precision read four times apart
  on it, where no gap of norms separates them. `grad_error_worst` and
  `grad_error_median` are the same per leaf, by the norm gaps' measure;
- `update_norm_gap`, `update_norm_gap_median`: the norm of each leaf's
  change after the last followed step, by the same two measures. Leaves whose reference gradient is
  under a thousandth of the median leaf's are left out of this one: under
  Adam they move by round-off alone.
"""

from __future__ import annotations

import statistics
from typing import Dict, Tuple

import numpy as np

from benchmark import cells, frames, weights

TINY_GRADIENT = 1e-3  # of the median leaf's gradient norm


def leaf_gaps(got: Dict[str, float], want: Dict[str, float], leaves=None) -> Dict[str, float]:
    """Per leaf: the gap between the two norms over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    leaves = list(want) if leaves is None else list(leaves)
    floor = statistics.median(want[k] for k in leaves)
    return {k: abs(got[k] - want[k]) / max(want[k], floor, 1e-30) for k in leaves}


def _diff_sq(got: dict, want: dict, k: str) -> float:
    """The estimated squared norm of the two first gradients' difference
    in leaf `k`."""
    return float(np.mean(np.square(got["grad_sketch"][k] - want["grad_sketch"][k])))


def sketch_gap(got: dict, want: dict) -> float:
    """The estimated norm of the difference of the two first gradients
    over the reference's norm, of the whole parameter vector."""
    diff = sum(_diff_sq(got, want, k) for k in want["grad"])
    return float(np.sqrt(diff / max(sum(v * v for v in want["grad"].values()), 1e-60)))


def sketch_gaps(got: dict, want: dict) -> Dict[str, float]:
    """Per leaf: the estimated norm of the difference of the two first
    gradients over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    floor = statistics.median(want["grad"].values())
    return {
        k: float(np.sqrt(_diff_sq(got, want, k))) / max(want["grad"][k], floor, 1e-30)
        for k in want["grad"]
    }


def worst_leaf(got: Dict[str, float], want: Dict[str, float], leaves=None) -> Tuple[float, str]:
    gaps = leaf_gaps(got, want, leaves)
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def median_leaf(got: Dict[str, float], want: Dict[str, float], leaves=None) -> float:
    return statistics.median(leaf_gaps(got, want, leaves).values())


def moved_leaves(want: dict):
    raw = want["grad_raw"]
    floor = TINY_GRADIENT * statistics.median(raw.values())
    return [k for k, v in raw.items() if v >= floor]


def compare(config: dict, got: dict, want: dict) -> Dict[str, Tuple[float, float]]:
    """name -> (value, limit). `got` and `want` hold `losses`, `grad` and
    `change`; `want` also `grad_raw`. A step's loss is compared where the
    configuration's file gives `loss_gap_<k>` a limit (PERF.md says which
    steps' losses are steady enough to hold one, and why)."""
    limits = config["check"]["limits"]
    if set(got["grad"]) != set(want["grad"]) or set(got["change"]) != set(want["change"]):
        raise ValueError("the program's parameter leaves are not the reference's")
    if len(got["losses"]) != len(want["losses"]):
        raise ValueError("the program and the reference followed different numbers of steps")
    numbers = all_numbers(got, want)
    return {k: (v, float(limits[k])) for k, v in numbers.items() if k in limits}


def all_numbers(got: dict, want: dict) -> Dict[str, float]:
    """Every number this comparison knows how to read; the configuration's
    limits say which of them are compared."""
    moved = moved_leaves(want)
    out = dict(loss_gaps(got, want))
    out["grad_norm_gap"] = worst_leaf(got["grad"], want["grad"])[0]
    out["grad_norm_gap_median"] = median_leaf(got["grad"], want["grad"])
    out["grad_error"] = sketch_gap(got, want)
    err = sketch_gaps(got, want)
    out["grad_error_worst"] = max(err.values())
    out["grad_error_median"] = statistics.median(err.values())
    out["update_norm_gap"] = worst_leaf(got["change"], want["change"], moved)[0]
    out["update_norm_gap_median"] = median_leaf(got["change"], want["change"], moved)
    return out


def loss_gaps(got: dict, want: dict) -> Dict[str, float]:
    return {
        f"loss_gap_{i}": abs(a - b)
        for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1)
    }


def notes(got: dict, want: dict) -> Dict[str, str]:
    """For the run's log, not compared: which leaf each norm's worst gap
    sits on, every step's loss on both sides, every number that has no
    limit, and each leaf's two gaps."""
    numbers = all_numbers(got, want)
    e = sketch_gaps(got, want)
    worst = {
        "grad_norm_gap": worst_leaf(got["grad"], want["grad"])[1],
        "grad_error_worst": max(e, key=e.get),
        "update_norm_gap": worst_leaf(got["change"], want["change"], moved_leaves(want))[1],
    }
    out = {k: f"{numbers[k]:.6g} worst leaf {name}" for k, name in worst.items()}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out[f"loss_gap_{i}"] = f"program {a:.6g} reference {b:.6g} gap {abs(a - b):.3g}"
    for k, v in numbers.items():
        out.setdefault(k, f"{v:.6g}")
    # Per leaf, raw: the reference's and the program's norm of the first
    # gradient, the estimated norm of their difference, the two norms of
    # the change (nan where the leaf is left out of it).
    moved = set(moved_leaves(want))
    floor = statistics.median(want["grad"].values())
    out["leaves"] = " ".join(
        "{}={:.4g}/{:.4g}/{:.4g}/{:.4g}/{:.4g}".format(
            k.split("core")[-1], want["grad"][k], got["grad"][k],
            e[k] * max(want["grad"][k], floor),
            want["change"][k] if k in moved else float("nan"), got["change"][k])
        for k in want["grad"])
    return out


def sketch_key(seed: int):
    """The key both sides sketch their first gradient under."""
    import jax

    return jax.random.fold_in(weights.seed_key(seed), 1)


def reference_readings(config: dict, seed: int, rows: dict, n_steps: int, mesh=None,
                       quant=None, fault=None, bench=None, root: str = cells.ROOT) -> dict:
    """The reference's side: the module the configuration names, weights
    and rows from the seed, nothing of the program's. On a mesh of several
    chips the same plain step runs with its rows spread over them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ref = cells.load_module(bench or cells.load_benchmark(root), "references",
                            config["reference"], root)
    B = rows["rewards"].shape[0] // n_steps
    batches = [frames.rows_slice(rows, i * B, (i + 1) * B) for i in range(n_steps)]
    shardings = None
    replicated = NamedSharding(mesh, P()) if mesh is not None else None
    if mesh is not None and mesh.size > 1:
        shardings = (replicated, NamedSharding(mesh, P(mesh.axis_names[0])))
    params0 = weights.make_params(ref.param_shapes(config), seed, out_shardings=replicated)
    return ref.run_reference(config, params0, batches, sketch_key(seed), quant=quant,
                             fault=fault, shardings=shardings)


def compare_with_reference(bench: dict, root: str, config: dict, seed: int, rows: dict,
                           got: dict, mesh):
    want = reference_readings(config, seed, rows, int(config["check"]["reference_steps"]), mesh,
                              bench=bench, root=root)
    return compare(config, got, want), notes(got, want)
