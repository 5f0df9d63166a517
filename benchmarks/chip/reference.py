"""Plain reference of a configuration's forward pass, in ``jax.numpy``.

Built from the configuration's own layer graph and imports nothing of
the program: conv by ``lax.conv_general_dilated``, pooling by
``lax.reduce_window`` (floor mode), LRN across channels, fc by ``dot``,
elementwise adds and a softmax, all in float32.  The weights are drawn
from the seed by the scheme the configuration states (``weights``), not
taken from the program.

The configuration's ``layers`` are read in order; each is a dict with
``kind`` (conv, pool, lrn, flatten, fc, add or softmax) and a unique
``name`` (not ``"input"``), and the keys of its kind: conv ``out``,
``kernel``, ``stride``, ``pad``, ``relu``; pool ``pool`` (max or avg),
``kernel``, ``stride``, ``relu``; lrn ``size``, ``alpha``, ``beta``,
``k``; fc ``out``, ``relu``.  The keys below are optional, and a
configuration that states none of them is a chain:

=========  =========  ================================================
key        on         meaning (default)
=========  =========  ================================================
``inputs`` any layer  names of earlier layers, or ``"input"`` for the
                      frame, that the layer reads (the layer before
                      it; the frame for the first)
``add``    (a kind)   the elementwise sum of its 2 or more ``inputs``,
                      of equal shapes, then ReLU if ``relu`` (false)
``groups`` conv       grouped convolution: weights ``[out, in/groups,
                      kh, kw]``, ``feature_group_count=groups``; ``in``
                      and ``out`` divisible by it (1)
``pad``    pool       symmetric spatial padding ``[ph, pw]``: -inf for
                      max, zeros counted in the divisor for avg
                      (Caffe's rule); output ``(h+2ph-kh)//sh+1``
                      (``[0, 0]``)
``gain``   conv, fc   weights drawn as ``gain * N(0, 2/fan_in)``, with
                      the draw's order and keys unchanged (1: no
                      multiply)
=========  =========  ================================================

The last layer's output is the network's.  ``work.layer_shapes``
resolves the graph and checks it; this module, ``work.py`` and
``run.check_network`` read every key above.

``precision`` selects how every conv and fc contraction is computed:

* ``"highest"``: float32 at ``Precision.HIGHEST``, what the
  configuration states;
* ``"high"``: the control, one step below it: ``Precision.HIGH``, which
  a TPU computes as three bfloat16 passes.  (XLA's CPU backend computes
  float32 at either setting, so the control reads low only on a chip.)
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import work

#: the precision of every conv and fc contraction, by name
PRECISIONS = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH}


def weights_key(seed: int) -> jax.Array:
    """The PRNG key the weights are drawn from, for any whole ``seed``
    below 2**63: its low 31 bits make the key and the rest is folded
    in, so seeds above 32 bits stay distinct."""
    lo, hi = seed & 0x7FFFFFFF, seed >> 31
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def make_params(config: dict, seed: int) -> Dict[str, Dict[str, jax.Array]]:
    """The configuration's weights from ``seed``: for each conv and fc
    layer in order, split the key and draw He-normal weights
    (``N(0, 2/fan_in)``, times the layer's ``gain`` where it states
    one); biases are zero."""
    key = weights_key(seed)
    params = {}
    for row in work.layer_shapes(config):
        if "weight_shape" not in row:
            continue
        shape = tuple(row["weight_shape"])
        key, sub = jax.random.split(key)
        conv = row["kind"] == "conv"
        fan_in = int(np.prod(shape[1:])) if conv else shape[0]
        w = (2.0 / fan_in) ** 0.5 * jax.random.normal(sub, shape, jnp.float32)
        if "gain" in row:
            w = row["gain"] * w
        params[row["name"]] = {
            "w": w, "b": jnp.zeros((shape[0] if conv else shape[1],),
                                   jnp.float32)}
    return params


def _contract(op, a, b, precision: str):
    """``op(a, b)`` (a conv or a dot) at the named precision."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return op(a, b, precision=PRECISIONS[precision])


def _conv_op(layer):
    return lambda a, b, **kw: lax.conv_general_dilated(
        a, b, tuple(layer["stride"]),
        [(layer["pad"][0],) * 2, (layer["pad"][1],) * 2],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=layer.get("groups", 1), **kw)


def _pool(x, layer):
    kh, kw = layer["kernel"]
    sh, sw = layer["stride"]
    ph, pw = layer.get("pad", [0, 0])
    dims, strides = (1, 1, kh, kw), (1, 1, sh, sw)
    pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
    if layer["pool"] == "max":
        y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
    elif layer["pool"] == "avg":
        y = lax.reduce_window(x, 0.0, lax.add, dims, strides,
                              pads) / (kh * kw)
    else:
        raise ValueError(f"{layer['name']}: pool {layer['pool']!r}")
    return jnp.maximum(y, 0.0) if layer.get("relu") else y


def _lrn(x, layer):
    n = layer["size"]
    sq = lax.reduce_window(x * x, 0.0, lax.add, (1, n, 1, 1), (1, 1, 1, 1),
                           ((0, 0), (n // 2, n - 1 - n // 2), (0, 0), (0, 0)))
    return x / (layer["k"] + layer["alpha"] * sq) ** layer["beta"]


def forward(config: dict, params, x, precision: str = "highest"):
    """Class probabilities ``[N, num_classes]`` of frames ``x``
    ``[N, C, H, W]``."""
    outs = {"input": x}
    for layer in work.layer_shapes(config):
        kind = layer["kind"]
        xs = [outs[n] for n in layer["inputs"]]
        x = xs[0]
        if kind == "conv":
            p = params[layer["name"]]
            x = _contract(_conv_op(layer), x, p["w"], precision)
            x = x + p["b"][None, :, None, None]
            if layer["relu"]:
                x = jnp.maximum(x, 0.0)
        elif kind == "pool":
            x = _pool(x, layer)
        elif kind == "lrn":
            x = _lrn(x, layer)
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "fc":
            p = params[layer["name"]]
            x = _contract(jnp.dot, x, p["w"], precision) + p["b"]
            if layer["relu"]:
                x = jnp.maximum(x, 0.0)
        elif kind == "add":
            for y in xs[1:]:
                x = x + y
            if layer.get("relu"):
                x = jnp.maximum(x, 0.0)
        elif kind == "softmax":
            x = jax.nn.softmax(x, axis=-1)
        outs[layer["name"]] = x
    return x


def probabilities(config: dict, seed: int, frames: np.ndarray,
                  precision: str = "highest", block: int = 16) -> np.ndarray:
    """The reference's probabilities for every frame of ``frames``, run
    in blocks of ``block`` rows so that it fits beside nothing else."""
    params = make_params(config, seed)
    fn = jax.jit(partial(forward, config, precision=precision))
    return np.concatenate(list(_blocks(fn, params, frames, block)))


def _blocks(fn, params, frames, block) -> Iterator[np.ndarray]:
    n = len(frames)
    for i in range(0, n, block):
        chunk = frames[i:i + block]
        if len(chunk) < block:  # one compiled shape: pad the last block
            pad = np.zeros((block - len(chunk), *chunk.shape[1:]),
                           chunk.dtype)
            yield np.asarray(fn(params, np.concatenate([chunk, pad])))[
                :len(chunk)]
        else:
            yield np.asarray(fn(params, chunk))
