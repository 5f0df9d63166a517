"""The comparison that decides ``correct``.

What the timed path produced is compared with ``reference.py``, run on
the same frames after the window has closed:

* a ``closed`` run: every probability of every batch the window
  fetched;
* an ``open`` run: every served request's top-k classes and their
  probabilities, and every request that got no ``ImageResult``.

The numbers read, each against its limit in ``limits/<cell>.json``:

* ``logp_err``: the largest ``|log p - log p_ref|`` over the compared
  probabilities where ``p`` or ``p_ref`` is at least ``LOGP_FLOOR``
  (so a class served with a high probability that the reference gives
  almost none counts in full): the error of the logits
  as the softmax passes it on, which does not shrink with a
  probability's size.  (The largest ``|p - p_ref|`` separated the
  control from the program by 7 to 10 times on the chip, this by 21 to
  93 times: ``PERF.md`` §6.)
* ``missing`` (open runs): requests that got no ``ImageResult``
  (never answered, shed or failed).  Its limit is 0.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

#: probabilities below this on both sides are left out: there the
#: logarithm reads the rounding of tiny numbers, not the logits' error
LOGP_FLOOR = 1e-4


def _logp_err(p: np.ndarray, ref: np.ndarray) -> float:
    p = np.asarray(p, np.float64)
    ref = np.asarray(ref, np.float64)
    keep = np.maximum(p, ref) >= LOGP_FLOOR
    logp = (np.log(np.maximum(p[keep], 1e-300))
            - np.log(np.maximum(ref[keep], 1e-300)))
    return float(np.max(np.abs(logp))) if logp.size else 0.0


def closed_readings(rec: dict, ref: np.ndarray) -> Dict[str, float]:
    """Readings of a closed run; ``ref`` holds the reference's
    probabilities of every pool frame."""
    b = rec["batch"]
    idx = np.concatenate([np.arange(first, first + b)
                          for first, _, _ in rec["batches"]])
    return {"logp_err": _logp_err(np.concatenate(rec["outputs"]), ref[idx])}


def open_readings(rec: dict, ref: np.ndarray) -> Dict[str, float]:
    """Readings of an open run; ``ref`` holds the reference's
    probabilities of every pool frame."""
    n_pool = len(ref)
    served, got, want = 0, [], []
    for rid in range(len(rec["due"])):
        res = rec["results"].get(rid)
        if getattr(res, "top_indices", None) is None or np.isnan(
                rec["done"][rid]):
            continue  # shed, failed or never answered
        served += 1
        r = ref[rid % n_pool]
        idx = np.asarray(res.top_indices)
        got.append(np.asarray(res.top_probs))
        want.append(r[idx])
    err = _logp_err(np.concatenate(got), np.concatenate(want)) if got else 0.0
    return {"logp_err": err, "missing": float(len(rec["due"]) - served)}


def decide(readings: Dict[str, float],
           limits: Dict[str, float]) -> Tuple[bool, List[dict]]:
    """``correct`` and each compared number beside its limit.  A number
    is within its limit when it is at most the limit; a reading that is
    not a number fails."""
    rows = []
    ok = True
    for name, limit in limits.items():
        value = readings[name]
        fine = bool(np.isfinite(value) and value <= limit)
        ok &= fine
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows
