"""The model's work, computed from a configuration's own layer list.

FLOPs follow ``repro.core.cost`` (copied, not imported, so that a
change to the program cannot change the yardstick): a conv or fc layer
does 2 x MACs, a pool ``c*oh*ow*kh*kw`` window operations, an LRN
``c*h*w*(n+4)``.  Bytes are the algorithm's minimum at the dtype the
configuration states: the layer's input and output activations and its
weights, each read or written once, with no padding, halo or overfetch.
A fused group's minimum is its first input, its last output and all its
weights; the intermediates never leave the chip.

Layers fall into families by kind: ``conv`` (conv, pool and LRN layers,
which the program runs as fused conv kernels) and ``fc``.  The
configuration groups each family's layers as the plan runs them
(``kernels.<family>.groups``).  The softmax and flatten layers do no
work that is counted here.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

#: the family of each layer kind whose work is counted
FAMILY = {"conv": "conv", "pool": "conv", "lrn": "conv", "fc": "fc"}


def _conv_out(h: int, w: int, layer: dict) -> Tuple[int, int]:
    kh, kw = layer["kernel"]
    sh, sw = layer.get("stride", [1, 1])
    ph, pw = layer.get("pad", [0, 0])
    return (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1


def _pool_out(h: int, w: int, layer: dict) -> Tuple[int, int]:
    kh, kw = layer["kernel"]
    sh, sw = layer["stride"]
    return (h - kh) // sh + 1, (w - kw) // sw + 1


def layer_shapes(config: dict) -> List[dict]:
    """Each layer of ``config`` with its input and output activation
    shape (``[C, H, W]`` while spatial, ``[D]`` once flattened) and, for
    conv and fc layers, its weight shape (conv OIHW, fc ``[d_in,
    d_out]``)."""
    shape = list(config["input"])
    out = []
    for layer in config["layers"]:
        kind = layer["kind"]
        row = dict(layer, in_shape=list(shape))
        if kind == "conv":
            c, h, w = shape
            kh, kw = layer["kernel"]
            row["weight_shape"] = [layer["out"], c, kh, kw]
            shape = [layer["out"], *_conv_out(h, w, layer)]
        elif kind == "pool":
            c, h, w = shape
            shape = [c, *_pool_out(h, w, layer)]
        elif kind == "flatten":
            n = 1
            for d in shape:
                n *= d
            shape = [n]
        elif kind == "fc":
            if len(shape) != 1:
                raise ValueError(f"{layer['name']}: fc needs a flattened input")
            row["weight_shape"] = [shape[0], layer["out"]]
            shape = [layer["out"]]
        elif kind not in ("lrn", "softmax"):
            raise ValueError(f"{layer['name']}: unknown layer kind {kind!r}")
        row["out_shape"] = list(shape)
        out.append(row)
    if shape != [config["num_classes"]]:
        raise ValueError(f"{config['name']}: output {shape}, expected "
                         f"[{config['num_classes']}]")
    return out


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def conv_macs(layer: dict) -> int:
    """Multiply-accumulates of one frame through a conv layer with shapes
    (``layer_shapes`` row): ``oh*ow*oc*cin*kh*kw``."""
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
    bytes (the first layer's input, the last layer's output and every
    weight, at the configuration's activation and weight dtypes)."""
    act = DTYPE_BYTES[config["dtype"]["activations"]]
    wb = DTYPE_BYTES[config["dtype"]["weights"]]
    rows = {row["name"]: row for row in layer_shapes(config)}
    out: Dict[str, List[dict]] = {}
    for family, spec in config["kernels"].items():
        for names in spec["groups"]:
            layers = [rows[n] for n in names]
            for row in layers:
                if FAMILY.get(row["kind"]) != family:
                    raise ValueError(f"{row['name']}: a {row['kind']} layer "
                                     f"in a {family} group")
            out.setdefault(family, []).append({
                "name": "+".join(names),
                "flops": batch * sum(layer_flops(r) for r in layers),
                "bytes": (batch * act * (_numel(layers[0]["in_shape"])
                                         + _numel(layers[-1]["out_shape"]))
                          + wb * sum(weight_elems(r) for r in layers)),
            })
    counted = [n for spec in config["kernels"].values()
               for names in spec["groups"] for n in names]
    missing = [r["name"] for r in rows.values()
               if r["kind"] in FAMILY and r["name"] not in counted]
    if missing or len(counted) != len(set(counted)):
        raise ValueError(f"{config['name']}: every conv, pool, lrn and fc "
                         f"layer belongs to exactly one group (missing "
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
