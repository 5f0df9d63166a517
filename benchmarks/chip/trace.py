"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

What a TPU trace holds, as JAX 0.9 with libtpu writes it: one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
operation the chip ran, named by the operation's HLO text
(``%conv2d.2 = f32[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...``).  A Pallas kernel is an operation whose text
names the target ``tpu_custom_call``; its instruction name
(``conv2d.2``) starts with the kernel's name (``conv2d``).  Asynchronous
copies sit on a line of their own and are not counted as busy.  The
host's plane ``/host:CPU`` carries the benchmark's spans
(``bench.<what>``); they share the device's clock.

``summarize`` reads, inside the span ``bench.window``:

* ``busy_s``: the union of the chip's operation intervals, averaged
  over the chips used; ``window_s``: the span's length;
* ``family_s``: the device time of the kernels of each family of the
  configuration (``kernels.<family>.match`` lists kernel names), and
  ``xla_s``: the device time of every operation that is not a kernel;
* ``breakdown``: the ten operations that took most device time (kernels
  by instruction name, other operations by kind), and the idle time
  between operations grouped by the innermost benchmark span the host
  was in at the gap's middle.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+)\s*=")
_SUFFIX = re.compile(r"\.\d+$")

def peaks_for(device_kind: str, base: Path) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; a kind that is
    not in the table is an error."""
    table = json.loads((base / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def start(trace_dir: str) -> None:
    """Start the profiler: the device, and the host's spans without
    Python call tracing (which would add an event per Python call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction(op_text: str) -> str:
    """``conv2d.2`` from ``%conv2d.2 = f32[...] custom-call(...)``."""
    m = _INSTR.match(op_text)
    return m.group(1) if m else op_text.split(" ", 1)[0]


def load(path) -> Tuple[Dict[str, List[tuple]], List[tuple]]:
    """``({chip_plane: [(op_text, start_ns, end_ns)]}, [(span, start_ns,
    end_ns)])`` from an ``.xplane.pb``: every device operation, and every
    host event whose name starts with ``bench.``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return device, host


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _family(op: str, match: Dict[str, List[str]]) -> str:
    base = _SUFFIX.sub("", instruction(op))
    for family, names in match.items():
        if base in names:
            return family
    return "other_kernels"


def reduce(device: Dict[str, List[tuple]], host: List[tuple],
           match: Dict[str, List[str]], n_chips: int) -> dict:
    """The numbers of ``summarize`` from loaded events."""
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    w0, w1 = windows[0]
    chips = sorted(device, key=lambda p: int(p.rsplit(":", 1)[1]))[:n_chips]
    if not chips:
        raise ValueError("no TPU operations in the trace")
    spans = sorted((s, e, name) for name, s, e in host
                   if name != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    busy_ns = 0.0
    family_ns: Dict[str, float] = defaultdict(float)
    xla_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    for chip in chips:
        ops = [(t, max(s, w0), min(e, w1)) for t, s, e in device[chip]
               if e > w0 and s < w1]
        busy = _union([(s, e) for _, s, e in ops])
        busy_ns += sum(e - s for s, e in busy)
        for text, s, e in ops:
            if KERNEL_MARK in text:
                family_ns[_family(text, match)] += e - s
                op_ns[instruction(text)] += e - s
            else:
                xla_ns += e - s
                op_ns[_SUFFIX.sub("", instruction(text))] += e - s
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gap_ns[_span_at(spans, starts, (g0 + g1) / 2)] += g1 - g0
    n = len(chips)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "family_s": {k: v * 1e-9 / n for k, v in family_ns.items()},
        "xla_s": xla_ns * 1e-9 / n,
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n] for k, v in top],
            "idle_gaps": [[k, v * 1e-9 / n] for k, v in gaps]},
    }


def _span_at(spans: List[tuple], starts: List[float], t: float) -> str:
    """The benchmark span open at time ``t`` (the benchmark's spans do
    not nest), or ``"no span"``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return "no span"


def summarize(path, config: dict, n_chips: int) -> dict:
    """``reduce`` of the trace at ``path`` with the configuration's
    kernel names."""
    match = {f: spec["match"] for f, spec in config["kernels"].items()}
    device, host = load(path)
    return reduce(device, host, match, n_chips)
