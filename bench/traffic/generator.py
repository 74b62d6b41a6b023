"""One general generator for every traffic mix.

A mix is a data file, ``bench/traffic/<name>.json``, of parameters:

    {"loop": "open" | "closed",
     "reports": ["ttft_p90_ms"],   # end-to-end metrics besides setup_s
     "rate_per_s": 2.8,            # open loop: mean arrivals per second
     "clients": 16,                # closed loop: requests in flight
     "items": 512,                 # how many items to draw
     "tenants": {"a": 0.6, "b": 0.4},
     "fields": {"prompt_len": {"dist": "lognormal", "median": 1024,
                               "sigma": 1.0, "min": 128, "max": 2048,
                               "int": true},
                "scale": {"dist": "uniform", "low": 0.5, "high": 1.5}}}

Every seed gets the same multiset of field values, inter-arrival gaps and
tenant counts, drawn at evenly spaced quantiles of each distribution, in an
order shuffled by the seed.  So the seed changes which item comes when, and
never how much work a run holds.  In an open loop each item carries its due
time ``due_s`` from the start of the window; the driver times a request
from it and records how late it was sent.  ``reports`` names the
end-to-end metrics that a cell under this mix reports (``setup_s`` always
comes too): the harness prints those, and reads each per-layer metric that
moves one of them.
"""
from __future__ import annotations

import json
import pathlib
from statistics import NormalDist
from typing import Any, Dict, List, Mapping

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent


def load(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Dict[str, Any]:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    spec = json.loads(path.read_text())
    if spec.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path.name}: loop must be 'open' or 'closed'")
    reports = spec.get("reports")
    if not reports or not all(isinstance(r, str) for r in reports):
        raise ValueError(f"{path.name}: reports must list the end-to-end "
                         "metrics the mix's cells report")
    return spec


def quantiles(dist: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` values at the evenly spaced quantiles (i + 0.5) / n of
    ``dist``, clipped to its ``min``/``max`` and rounded where ``int``."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        v = dist["low"] + p * (dist["high"] - dist["low"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "exponential":
        v = -np.log1p(-p) * dist["mean"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    v = np.clip(v, dist.get("min", -np.inf), dist.get("max", np.inf))
    return np.rint(v).astype(np.int64) if dist.get("int") else v


def draw(spec: Mapping[str, Any], seed: int) -> List[Dict[str, Any]]:
    """The mix's items for ``seed``, in the order they are sent."""
    n = int(spec["items"])
    rng = np.random.default_rng(seed)
    items: List[Dict[str, Any]] = [{"index": i} for i in range(n)]
    tenants = spec.get("tenants") or {"tenant-0": 1.0}
    names = list(tenants)
    share = np.array([tenants[t] for t in names], float)
    counts = np.floor(share / share.sum() * n).astype(int)
    counts[np.argmax(share)] += n - counts.sum()
    owner = rng.permutation(np.repeat(np.arange(len(names)), counts))
    for it, k in zip(items, owner):
        it["tenant"] = names[k]
    for field, dist in sorted(spec.get("fields", {}).items()):
        vals = rng.permutation(quantiles(dist, n))
        for it, v in zip(items, vals):
            it[field] = v.item()
    if spec["loop"] == "open":
        gaps = rng.permutation(
            quantiles({"dist": "exponential",
                       "mean": 1.0 / float(spec["rate_per_s"])}, n))
        for it, t in zip(items, np.cumsum(gaps)):
            it["due_s"] = float(t)
    return items
