"""A one-entry on-disk store for objects made of dataclasses and arrays.

``save(obj, path, key)`` writes every array of ``obj`` as its own
``.npy`` file and the structure as ``manifest.json``; ``load(path, key)``
rebuilds the object when the manifest carries the same key and returns
None otherwise.  Dataclasses are named by module and qualified name and
rebuilt through their constructor, so the store follows the fields that
the program's classes have, whatever they are.  The manifest is written
last, so an entry cut off half-way is never read as whole.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import shutil
from pathlib import Path

import numpy as np

__all__ = ["save", "load"]


def _encode(obj, path: Path, name: str):
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        cls = type(obj)
        return {"__dc__": f"{cls.__module__}:{cls.__qualname__}",
                "fields": {f.name: _encode(getattr(obj, f.name), path,
                                           f"{name}.{f.name}")
                           for f in dataclasses.fields(obj)}}
    if isinstance(obj, dict):
        return {"__dict__": {k: _encode(v, path, f"{name}.{k}")
                             for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"__list__": [_encode(v, path, f"{name}.{i}")
                             for i, v in enumerate(obj)],
                "tuple": isinstance(obj, tuple)}
    if hasattr(obj, "__array__") and not np.isscalar(obj):
        fname = f"{name}.npy"
        np.save(path / fname, np.asarray(obj), allow_pickle=False)
        return {"__npy__": fname}
    if isinstance(obj, np.generic):
        return obj.item()
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    raise TypeError(f"cannot store {type(obj).__name__} at {name}")


def _decode(node, path: Path):
    if not isinstance(node, dict):
        return node
    if "__npy__" in node:
        return np.load(path / node["__npy__"], allow_pickle=False)
    if "__dc__" in node:
        mod, qual = node["__dc__"].split(":")
        cls = importlib.import_module(mod)
        for part in qual.split("."):
            cls = getattr(cls, part)
        return cls(**{k: _decode(v, path) for k, v in node["fields"].items()})
    if "__dict__" in node:
        return {k: _decode(v, path) for k, v in node["__dict__"].items()}
    if "__list__" in node:
        vals = [_decode(v, path) for v in node["__list__"]]
        return tuple(vals) if node["tuple"] else vals
    raise ValueError(f"unreadable store node {sorted(node)}")


def save(obj, path: Path, key: str) -> None:
    """Replace whatever entry ``path`` holds with ``obj`` under ``key``."""
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    tree = _encode(obj, path, "root")
    tmp = path / "manifest.json.tmp"
    tmp.write_text(json.dumps({"key": key, "tree": tree}))
    tmp.replace(path / "manifest.json")


def load(path: Path, key: str):
    """The stored object when ``path`` holds an entry under ``key``."""
    man = path / "manifest.json"
    if not man.exists():
        return None
    doc = json.loads(man.read_text())
    if doc.get("key") != key:
        return None
    return _decode(doc["tree"], path)
