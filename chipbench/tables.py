"""The configuration's packed tables, built by the program and kept in a
cache under ``chipbench/.cache`` (git ignores it).

Determinizing a large rule set is host work the program does on every
start (12 s for the PROSITE pack on one host core); the cache keeps it out
of every run but a checkout's first.  The key covers the rule set and the
source of the program's ``repro.core`` package, so a change to either
builds afresh.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib

import numpy as np

CACHE = pathlib.Path(__file__).resolve().parent / ".cache" / "tables"


def _key(cfg: dict) -> str:
    import repro.core
    h = hashlib.sha256(json.dumps([cfg["patterns"], cfg["search"]],
                                  sort_keys=True).encode())
    core = pathlib.Path(repro.core.__file__).parent
    for src in sorted(core.rglob("*.py")):
        h.update(src.relative_to(core).as_posix().encode())
        h.update(src.read_bytes())
    return h.hexdigest()[:32]


def packed_tables(cfg: dict):
    """The config's patterns as one ``PackedDFA`` (from the cache when it
    holds this key).  Returns ``(packed, cache_hit)``."""
    from repro.core import PatternSet
    from repro.core.automata import PackedDFA

    path = CACHE / f"{_key(cfg)}.npz"
    if path.is_file():
        with np.load(path) as z:
            return PackedDFA(**{k: z[k] for k in z.files}), True
    ps = PatternSet({p["name"]: p["regex"] for p in cfg["patterns"]},
                    k_blk=1 << 30, search=bool(cfg["search"]))
    packed = ps.blocks[0]
    CACHE.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **{f.name: getattr(packed, f.name)
                     for f in dataclasses.fields(packed)})
    tmp.replace(path)
    return packed, False
