"""The model's work, computed from a configuration's own layer graph.

FLOPs follow ``repro.core.cost`` (copied, not imported, so that a
change to the program cannot change the yardstick): a conv or fc layer
does 2 x MACs (a grouped conv's MACs divide by its ``groups``), a pool
``c*oh*ow*kh*kw`` window operations, an LRN ``c*h*w*(n+4)``, an add of
``k`` inputs ``(k-1)*c*h*w``.  Bytes are the algorithm's minimum at the
dtype the configuration states: activations and weights, each read or
written once, with no padding, halo or overfetch.  A fused group's
minimum is every distinct tensor it reads from outside it, once; every
tensor it writes that a layer outside it reads, or that is the
network's output; and all its weights.  The intermediates never leave
the chip.  For a chain of layers that is its first input and its last
output.

Layers fall into families by kind: ``conv`` (conv, pool, LRN and add
layers, which the program runs as fused conv kernels and their
epilogues) and ``fc``.  The configuration groups each family's layers
as the plan runs them (``kernels.<family>.groups``).  The softmax and
flatten layers do no work that is counted here.  ``reference.py``
documents the configuration's keys.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

#: the family of each layer kind whose work is counted
FAMILY = {"conv": "conv", "pool": "conv", "lrn": "conv", "add": "conv",
          "fc": "fc"}


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _window_out(h: int, w: int, layer: dict) -> Tuple[int, int]:
    """Output height and width of a conv or pool window (floor mode)."""
    kh, kw = layer["kernel"]
    sh, sw = layer.get("stride", [1, 1])
    ph, pw = layer.get("pad", [0, 0])
    return (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1


def layer_shapes(config: dict) -> List[dict]:
    """Each layer of ``config`` with ``inputs``, the names of the layers
    it reads (``"input"`` for the frame; by default the layer before
    it), its input and output activation shape (``[C, H, W]`` while
    spatial, ``[D]`` once flattened) and, for conv and fc layers, its
    weight shape (conv OIHW with ``in/groups`` input channels, fc
    ``[d_in, d_out]``).  The one walk of the graph in the yardstick:
    it refuses a name used twice, an input that is not an earlier layer,
    and an add whose inputs differ in shape."""
    shapes = {"input": list(config["input"])}
    prev = "input"
    out = []
    for layer in config["layers"]:
        kind, name = layer["kind"], layer["name"]
        if name in shapes:
            raise ValueError(f"{name}: a name used twice or reserved")
        inputs = list(layer.get("inputs", [prev]))
        unknown = [n for n in inputs if n not in shapes]
        if unknown:
            raise ValueError(f"{name}: reads {unknown}, not earlier layers")
        in_shapes = [shapes[n] for n in inputs]
        if kind == "add":
            if len(inputs) < 2 or any(s != in_shapes[0] for s in in_shapes):
                raise ValueError(f"{name}: an add sums 2 or more inputs of "
                                 f"one shape, not {in_shapes}")
        elif len(inputs) != 1:
            raise ValueError(f"{name}: a {kind} layer reads one input, "
                             f"not {inputs}")
        shape = in_shapes[0]
        row = dict(layer, inputs=inputs, in_shape=list(shape))
        if kind == "conv":
            c, h, w = shape
            kh, kw = layer["kernel"]
            groups = layer.get("groups", 1)
            if c % groups or layer["out"] % groups:
                raise ValueError(f"{name}: {c} in and {layer['out']} out "
                                 f"channels in {groups} groups")
            row["weight_shape"] = [layer["out"], c // groups, kh, kw]
            shape = [layer["out"], *_window_out(h, w, layer)]
        elif kind == "pool":
            c, h, w = shape
            shape = [c, *_window_out(h, w, layer)]
        elif kind == "flatten":
            shape = [_numel(shape)]
        elif kind == "fc":
            if len(shape) != 1:
                raise ValueError(f"{name}: fc needs a flattened input")
            row["weight_shape"] = [shape[0], layer["out"]]
            shape = [layer["out"]]
        elif kind not in ("lrn", "softmax", "add"):
            raise ValueError(f"{name}: unknown layer kind {kind!r}")
        row["out_shape"] = list(shape)
        shapes[name] = list(shape)
        prev = name
        out.append(row)
    if shape != [config["num_classes"]]:
        raise ValueError(f"{config['name']}: output {shape}, expected "
                         f"[{config['num_classes']}]")
    return out


def conv_macs(layer: dict) -> int:
    """Multiply-accumulates of one frame through a conv layer with shapes
    (``layer_shapes`` row): ``oh*ow*oc*cin*kh*kw``, where ``cin`` is the
    input channels of one group."""
    oc, cin, kh, kw = layer["weight_shape"]
    _, oh, ow = layer["out_shape"]
    return oh * ow * oc * cin * kh * kw


def layer_flops(layer: dict) -> int:
    """FLOPs of one frame through one layer (``layer_shapes`` row)."""
    kind = layer["kind"]
    if kind == "conv":
        return 2 * conv_macs(layer)
    if kind == "fc":
        d_in, d_out = layer["weight_shape"]
        return 2 * d_in * d_out
    if kind == "pool":
        c, oh, ow = layer["out_shape"]
        kh, kw = layer["kernel"]
        return c * oh * ow * kh * kw
    if kind == "lrn":
        return _numel(layer["out_shape"]) * (layer["size"] + 4)
    if kind == "add":
        return (len(layer["inputs"]) - 1) * _numel(layer["out_shape"])
    return 0


def weight_elems(layer: dict) -> int:
    """Weights and biases of one layer (0 for layers without)."""
    if "weight_shape" not in layer:
        return 0
    return _numel(layer["weight_shape"]) + layer["weight_shape"][
        0 if layer["kind"] == "conv" else 1]


def group_work(config: dict, batch: int) -> Dict[str, List[dict]]:
    """``{family: [{"name", "flops", "bytes"}, ...]}`` for one forward of
    ``batch`` frames, one entry per group of ``config["kernels"][family]
    ["groups"]``: the FLOPs of the group's layers, and its minimum HBM
    bytes (each distinct tensor it reads from outside it, each tensor it
    writes that is read outside it or is the network's output, and every
    weight, at the configuration's activation and weight dtypes)."""
    act = DTYPE_BYTES[config["dtype"]["activations"]]
    wb = DTYPE_BYTES[config["dtype"]["weights"]]
    rows = {row["name"]: row for row in layer_shapes(config)}
    size = {"input": _numel(config["input"])}
    size.update((n, _numel(r["out_shape"])) for n, r in rows.items())
    # the layers that read each tensor; the network's output is read by
    # its caller (None)
    readers: Dict[str, set] = {list(rows)[-1]: {None}}
    for row in rows.values():
        for n in row["inputs"]:
            readers.setdefault(n, set()).add(row["name"])
    out: Dict[str, List[dict]] = {}
    for family, spec in config["kernels"].items():
        for names in spec["groups"]:
            layers = [rows[n] for n in names]
            for row in layers:
                if FAMILY.get(row["kind"]) != family:
                    raise ValueError(f"{row['name']}: a {row['kind']} layer "
                                     f"in a {family} group")
            inside = set(names)
            reads = {n for r in layers for n in r["inputs"]} - inside
            writes = {n for n in names if readers.get(n, set()) - inside}
            out.setdefault(family, []).append({
                "name": "+".join(names),
                "flops": batch * sum(layer_flops(r) for r in layers),
                "bytes": (batch * act * (sum(size[n] for n in reads)
                                         + sum(size[n] for n in writes))
                          + wb * sum(weight_elems(r) for r in layers)),
            })
    counted = [n for spec in config["kernels"].values()
               for names in spec["groups"] for n in names]
    missing = [r["name"] for r in rows.values()
               if r["kind"] in FAMILY and r["name"] not in counted]
    if missing or len(counted) != len(set(counted)):
        raise ValueError(f"{config['name']}: every conv, pool, lrn, add and "
                         f"fc layer belongs to exactly one group (missing "
                         f"{missing})")
    return out


def flops_per_frame(config: dict) -> int:
    """Every counted FLOP of one frame's forward."""
    return sum(layer_flops(row) for row in layer_shapes(config))


def ideal_s(groups: List[dict], peaks: dict) -> float:
    """The least time the chip could take for a list of groups: for each,
    the larger of its FLOPs over the peak FLOP/s and its bytes over the
    peak bandwidth, summed."""
    return sum(max(g["flops"] / peaks["bf16_flops_per_s"],
                   g["bytes"] / peaks["hbm_bytes_per_s"]) for g in groups)
