"""Device time per plan step, from a traced window and the program's
map of its operations to plan steps.

The program runs each step of its plan under ``jax.named_scope(label)``
(``conv1+pool1+norm1``: the step's layer names joined by ``+``), and
``CNNEngine.op_steps`` reads, from the compiled bucket program, the step
of each of its instructions, or ``""`` for an instruction under no step
(the copies that layout assignment inserts).  A trace names each device
operation by its HLO text, whose instruction name is the key of that
map.  ``reduce`` sums the device seconds of the window's operations per
step, kernels (``tpu_custom_call``) and other operations apart, and the
seconds of operations under no step, by kind.

``plan_roofline_percent`` judges a family's steps as a whole: its ideal
time over the device time of every operation under a step of the
family, layout operations included.  A step is of a family when its
layer names meet one of the family's ``groups`` in the configuration.

A program without ``op_steps`` (an older engine) gives no map, and
every number here is then ``None``.  ``step_table.py`` runs a closed
cell on one chip and prints these numbers.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

import jax

from benchmarks.chip import readers, work
from benchmarks.chip import trace as tr


def op_steps(engine, params, pool, traffic: dict) -> Optional[Dict[str, str]]:
    """The engine's op→step map for a mix that sends batches of one size
    (``traffic["batch"]``), so that the window runs one bucket program;
    ``None`` for other mixes and for engines without the map."""
    if "batch" not in traffic or not hasattr(engine, "op_steps"):
        return None
    return engine.op_steps(params, jax.device_put(pool[:traffic["batch"]]))


def reduce(device: Dict[str, List[tuple]], host: List[tuple],
           steps: Dict[str, str], n_chips: int) -> dict:
    """``{"steps": {label: {"kernels_s", "other_s"}}, "unmapped_s",
    "unmapped_ops": {kind: s}}`` from the events of ``trace.load``:
    operations clipped to the ``bench.window`` span, seconds averaged
    over the chips used, steps in the program's order.  An operation
    whose instruction the map does not hold, or maps to ``""``, is
    unmapped."""
    windows = [(s, e) for name, s, e in host if name == tr.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {tr.WINDOW_SPAN} spans in the trace")
    w0, w1 = windows[0]
    chips = sorted(device, key=lambda p: int(p.rsplit(":", 1)[1]))[:n_chips]
    if not chips:
        raise ValueError("no TPU operations in the trace")
    step_ns: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"kernels_s": 0.0, "other_s": 0.0})
    unmapped_ns: Dict[str, float] = defaultdict(float)
    for chip in chips:
        for text, s, e in device[chip]:
            ns = min(e, w1) - max(s, w0)
            if ns <= 0:
                continue
            name = tr.instruction(text)
            label = steps.get(name, "")
            if not label:
                unmapped_ns[tr._SUFFIX.sub("", name)] += ns
            elif tr.KERNEL_MARK in text:
                step_ns[label]["kernels_s"] += ns
            else:
                step_ns[label]["other_s"] += ns
    n = len(chips)
    in_plan_order = dict.fromkeys(label for label in steps.values() if label)
    return {
        "steps": {label: {k: v * 1e-9 / n for k, v in step_ns[label].items()}
                  for label in in_plan_order if label in step_ns},
        "unmapped_s": sum(unmapped_ns.values()) * 1e-9 / n,
        "unmapped_ops": {k: v * 1e-9 / n for k, v in sorted(
            unmapped_ns.items(), key=lambda kv: -kv[1])},
    }


def _forwards(rec: dict) -> Optional[int]:
    t = rec["trace"]
    if t is None or not t.get("step_s") or readers.closed_frames(rec) is None:
        return None
    return len(rec["batches"])


def plan_roofline_percent(rec: dict, family: str) -> Optional[float]:
    """The family's ideal time over the device time of every operation
    under one of its plan steps, in percent, for a closed run's traced
    window; ``None`` where no operation maps to the family."""
    forwards = _forwards(rec)
    if forwards is None:
        return None
    layers = {name for names in rec["config"]["kernels"][family]["groups"]
              for name in names}
    seconds = sum(row["kernels_s"] + row["other_s"]
                  for label, row in rec["trace"]["step_s"]["steps"].items()
                  if layers & set(label.split("+")))
    if seconds <= 0:
        return None
    groups = work.group_work(rec["config"], rec["batch"])[family]
    return 100.0 * work.ideal_s(groups, rec["peaks"]) * forwards / seconds


def notes(rec: dict) -> List[str]:
    """One stderr line per plan step of a traced closed run, per
    forward, and one for the operations under no step."""
    forwards = _forwards(rec)
    if forwards is None:
        return []
    step_s = rec["trace"]["step_s"]
    out = [f"step {label}: kernels {1e3 * row['kernels_s'] / forwards:.4f}"
           f" ms, other ops {1e3 * row['other_s'] / forwards:.4f} ms per "
           f"forward" for label, row in step_s["steps"].items()]
    mapped = sum(r["kernels_s"] + r["other_s"]
                 for r in step_s["steps"].values())
    total = mapped + step_s["unmapped_s"]
    share = 100.0 * step_s["unmapped_s"] / total if total > 0 else 0.0
    top = ", ".join(f"{k} {1e3 * v / forwards:.4f}" for k, v in list(
        step_s["unmapped_ops"].items())[:5])
    out.append(f"under no step: {1e3 * step_s['unmapped_s'] / forwards:.4f}"
               f" ms per forward, {share:.3f}% of device time ({top})")
    return out
