"""Configurations and traffic mixes, found by name under this directory.

A configuration is ``configs/<name>.json``: the base machine fields, the
named machines, optional design axes crossed with every machine, and the
benchmarks with their thread counts. A traffic mix is
``workloads/<name>.json``. Both are plain data; nothing here imports the
program under test.
"""

from __future__ import annotations

import itertools
import json
import os
from typing import Dict, List, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


def find(kind: str, name: str, roots: Sequence[str] = (HERE,),
         ext: str = ".json") -> str:
    """``<root>/<kind>/<name><ext>`` in the first root that has it: a
    later addition is a new file in one of the roots."""
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {list(roots)}")


def _load(kind: str, name: str, roots: Sequence[str]) -> dict:
    with open(find(kind, name, roots)) as f:
        return json.load(f)


def load_config(name: str, roots: Sequence[str] = (HERE,)) -> dict:
    return _load("configs", name, roots)


def load_traffic(name: str, roots: Sequence[str] = (HERE,)) -> dict:
    return _load("workloads", name, roots)


def _value_tag(v) -> str:
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def machines(cfg: dict) -> Dict[str, dict]:
    """Machine name -> every machine field, in the configuration's order:
    machines-major, then the product of the axes in their listed order."""
    axes = cfg.get("axes") or {}
    keys = list(axes)
    out: Dict[str, dict] = {}
    for mname, fields in cfg["machines"].items():
        for values in itertools.product(*(axes[k] for k in keys)):
            name = mname
            if keys:
                name += "." + ".".join(
                    f"{k}={_value_tag(v)}" for k, v in zip(keys, values))
            m = dict(cfg["base"], **fields, **dict(zip(keys, values)))
            m["name"] = name
            out[name] = m
    return out


def benches(cfg: dict) -> List[str]:
    return list(cfg["benchmarks"])


def n_threads(cfg: dict) -> Dict[str, int]:
    return {b: v["n_threads"] for b, v in cfg["benchmarks"].items()}


def expansion_keys(cfg: dict) -> set:
    """Distinct (warp size, SIMD width, MIMD, transaction bytes): the
    machine fields that decide a workload's op streams."""
    return {(m["warp_size"], m["simd_width"], bool(m["mimd"]),
             m["transaction_bytes"]) for m in machines(cfg).values()}
