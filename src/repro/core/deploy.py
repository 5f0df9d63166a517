"""Model deployment pipeline (paper §2.2 / Fig. 2).

Train-side: serialize a trained model (net definition + weights) into a
device-ready directory — ``manifest.json`` (architecture, layer table,
dtype, version) + ``weights.npz``.  Device-side: load and verify, yielding
the exact structures the engine executes.  This is the Caffe→convert→
upload→execute path with JAX in both roles.

Also used by the transformer stack's checkpointing (``repro.train.checkpoint``
wraps the same format with sharding metadata).

A manifest may additionally carry a ``tuned_plan`` — the winning knob
set ``tools/autotune.py`` found for this network (per-layer methods,
``oh_block`` bands, fusion opt-outs), serialized canonically so the
round-trip is byte-exact.  ``load_model`` verifies the TUNED plan (not
just the default one) and ``load_engine`` reconstructs a pre-tuned
``CNNEngine`` — deployment serves the autotuned configuration without
re-searching.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.methods import Method
from repro.core.netdefs import LayerSpec, NetworkDef, NETWORKS
from repro.core.plan import compile_plan, infer_param_shapes

FORMAT_VERSION = 1

#: knob names a tuned plan may pin — exactly ``compile_plan``'s
#: configuration surface (engine-side, ``fuse`` maps onto ``fuse_pool``)
TUNED_KNOBS = ("method", "per_layer_methods", "oh_block",
               "per_layer_oh_blocks", "fuse", "fuse_relu", "per_layer_fuse",
               "per_layer_pool_carry", "per_layer_lrn_oc_block",
               "per_layer_oc_block_final", "use_pallas")


def knobs_to_manifest(knobs: dict) -> dict:
    """Serialize a ``compile_plan`` knob set for the manifest: ``Method``
    enums become their value strings, dict knobs sort canonically.
    Unknown knob names raise — a typo must not ship as a silently
    ignored tuning decision."""
    unknown = set(knobs) - set(TUNED_KNOBS)
    if unknown:
        raise ValueError(f"unknown tuned-plan knob(s): {sorted(unknown)}")
    out = {}
    for k in TUNED_KNOBS:
        if k not in knobs:
            continue
        v = knobs[k]
        if isinstance(v, Method):
            v = v.value
        elif isinstance(v, dict):
            v = {n: (m.value if isinstance(m, Method) else m)
                 for n, m in sorted(v.items())}
        out[k] = v
    return out


def knobs_from_manifest(d: dict) -> dict:
    """Inverse of ``knobs_to_manifest``: value strings back to ``Method``
    enums, ready to splat into ``compile_plan``."""
    out = dict(d)
    if "method" in out:
        out["method"] = Method(out["method"])
    if "per_layer_methods" in out:
        out["per_layer_methods"] = {
            n: Method(m) for n, m in out["per_layer_methods"].items()}
    return out


def _flatten(params: dict, prefix: str = "") -> Dict[str, np.ndarray]:
    flat = {}
    for k, v in params.items():
        key = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(_flatten(v, key))
        else:
            flat[key] = np.asarray(v)
    return flat


def _unflatten(flat: Dict[str, np.ndarray]) -> dict:
    out: dict = {}
    for k, v in flat.items():
        parts = k.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = jnp.asarray(v)
    return out


def save_model(path, net: NetworkDef, params: dict, extra: dict = None,
               tuned: dict = None) -> None:
    """Train-side conversion: write the deployable artifact.  ``tuned``
    (optional) is a ``compile_plan`` knob set (``Method`` enums welcome)
    persisted under ``manifest["tuned_plan"]`` — the autotuner's winning
    configuration, reconstructed verbatim by ``load_engine``."""
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    flat = _flatten(params)
    np.savez(path / "weights.npz", **flat)
    digest = hashlib.sha256()
    for k in sorted(flat):
        digest.update(k.encode())
        digest.update(flat[k].tobytes())
    manifest = {
        "format_version": FORMAT_VERSION,
        "network": dataclasses.asdict(net),
        "tensors": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                    for k, v in flat.items()},
        "weights_sha256": digest.hexdigest(),
        "extra": extra or {},
    }
    if tuned is not None:
        manifest["tuned_plan"] = knobs_to_manifest(tuned)
    (path / "manifest.json").write_text(
        json.dumps(manifest, indent=2, sort_keys=True))


def load_model(path) -> Tuple[NetworkDef, dict, dict]:
    """Device-side load: verify integrity, rebuild net + params."""
    path = Path(path)
    manifest = json.loads((path / "manifest.json").read_text())
    if manifest["format_version"] != FORMAT_VERSION:
        raise ValueError(f"format version {manifest['format_version']}")
    data = np.load(path / "weights.npz")
    flat = {k: data[k] for k in data.files}
    digest = hashlib.sha256()
    for k in sorted(flat):
        digest.update(k.encode())
        digest.update(flat[k].tobytes())
    if digest.hexdigest() != manifest["weights_sha256"]:
        raise ValueError("weight checksum mismatch — corrupted artifact")
    for k, meta in manifest["tensors"].items():
        if list(flat[k].shape) != meta["shape"]:
            raise ValueError(f"tensor {k} shape mismatch")
        if str(flat[k].dtype) != meta["dtype"]:
            # a dtype-corrupted artifact (e.g. re-saved at lower precision
            # with a refreshed checksum) must not load silently
            raise ValueError(
                f"tensor {k} dtype mismatch: manifest records "
                f"{meta['dtype']}, weights.npz holds {flat[k].dtype}")
    nd = manifest["network"]
    net = NetworkDef(
        name=nd["name"],
        input_shape=tuple(nd["input_shape"]),
        num_classes=nd["num_classes"],
        layers=tuple(
            LayerSpec(**{**l, "kernel": tuple(l["kernel"]),
                         "stride": tuple(l["stride"]),
                         "padding": tuple(l["padding"]),
                         "inputs": tuple(l.get("inputs", ()))})
            for l in nd["layers"]
        ),
    )
    # the declared architecture must size the shipped tensors: a tampered
    # layer table (wrong kernel, channel count, fc fan-in) fails HERE,
    # not at first inference with a cryptic dot-shape error
    for name, shp in infer_param_shapes(net).items():
        spec = next(l for l in net.layers if l.name == name)
        b_shape = (shp[0],) if spec.kind == "conv" else (shp[1],)
        for key, want in ((f"{name}/w", tuple(shp)), (f"{name}/b", b_shape)):
            meta = manifest["tensors"].get(key)
            got = None if meta is None else tuple(meta["shape"])
            if got != want:
                raise ValueError(
                    f"manifest geometry mismatch: tensor {key} must be "
                    f"{want} for the declared architecture, manifest "
                    f"records {got}")
    # static plan verification: shape flow, band coverage, VMEM audit
    # (PlanVerificationError is a ValueError — corrupt geometry fails
    # the load exactly like a checksum or dtype mismatch).  A tuned
    # manifest is verified under ITS knobs — a tampered tuning that
    # compiles to broken geometry fails the load, not the first batch.
    tuned = manifest.get("tuned_plan")
    if tuned is not None:
        kn = knobs_from_manifest(tuned)
        kn.setdefault("verify", True)
        compile_plan(net, **kn)
    else:
        compile_plan(net, verify=True)
    return net, _unflatten(flat), manifest["extra"]


def load_tuned_knobs(path) -> Optional[dict]:
    """The deserialized ``tuned_plan`` knob set of an artifact, or None
    for an untuned manifest.  Reads only the manifest — no weight I/O."""
    manifest = json.loads((Path(path) / "manifest.json").read_text())
    tuned = manifest.get("tuned_plan")
    return None if tuned is None else knobs_from_manifest(tuned)


def load_engine(path) -> Tuple["object", dict, Optional[dict]]:
    """Device-side one-call bring-up: ``(engine, params, tuned_knobs)``
    with the ``CNNEngine`` already configured to the manifest's tuned
    plan (default heuristics when the artifact carries none) — serving
    starts on the autotuned configuration without re-searching."""
    from repro.core.engine import CNNEngine

    net, params, _extra = load_model(path)
    knobs = load_tuned_knobs(path)
    kwargs = dict(knobs or {})
    if "fuse" in kwargs:  # compile_plan's name; the engine calls it fuse_pool
        kwargs["fuse_pool"] = kwargs.pop("fuse")
    engine = CNNEngine(net, **kwargs)
    return engine, params, knobs
