"""One general generator: a configuration file's ``fields`` to fragment
files on disk and a ``Reference`` over the same generated arrays.

Each field has a ``kind``, a module ``benchmark/kinds/<kind>.py`` found
by name, with ``generate(cfg, rng, shard) -> {name: array}``,
``fragments(cfg, data, shard) -> [(view, position chunks, ranked
cache?)]``, ``meta(cfg) -> dict | None``, ``reference(cfg, stacked) ->``
a field of ``benchmark.reference`` and ``row_bits(cfg, shards)`` for the
roofline's byte count. A new kind is a new file there.

Everything is drawn from ``--seed``: field i of shard s uses
``default_rng([seed, s, i])``. Builder processes stay off JAX.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

from benchmark.reference import Reference


def field_of(config: dict, name: str) -> dict:
    for f in config["fields"]:
        if f["name"] == name:
            return f
    raise ValueError(f"configuration {config['name']} has no field {name!r}")


def kind_of(field_cfg: dict):
    return importlib.import_module(f"benchmark.kinds.{field_cfg['kind']}")


def _fragment_dir(data_dir: str, index: str, field: str, view: str) -> str:
    return os.path.join(data_dir, index, field, "views", view, "fragments")


def generate_shard(config: dict, seed: int, shard: int) -> list[dict]:
    """The generated arrays of one shard, one dict per field."""
    return [
        kind_of(f).generate(f, np.random.default_rng([seed, shard, i]), shard)
        for i, f in enumerate(config["fields"])
    ]


def build_shard(config: dict, seed: int, shard: int, data_dir: str):
    """Write one shard's fragment files; return what the reference
    needs. Runs in a spawned builder process."""
    from pilosa_tpu.roaring import build_fragment_file

    data = generate_shard(config, seed, shard)
    bits = 0
    for f, d in zip(config["fields"], data):
        for view, chunks, ranked in kind_of(f).fragments(f, d, shard):
            path = os.path.join(
                _fragment_dir(data_dir, config["index"], f["name"], view), str(shard)
            )
            os.makedirs(os.path.dirname(path), exist_ok=True)
            bits += build_fragment_file(path, chunks, write_cache_file=ranked)["bits"]
    if "jax" in sys.modules:
        raise RuntimeError("the data builder imported JAX")
    return shard, data, bits


def reference_of(config: dict, per_shard: list[list[dict]]) -> Reference:
    """Stack the shards' arrays and hand each field to its kind."""
    fields = {}
    for i, f in enumerate(config["fields"]):
        names = per_shard[0][i].keys()
        stacked = {n: np.stack([shard[i][n] for shard in per_shard]) for n in names}
        fields[f["name"]] = kind_of(f).reference(f, stacked)
    return Reference(fields)


def build(config: dict, seed: int, data_dir: str, shards: int | None = None):
    """(Reference, facts about the build). ``shards`` overrides the
    configuration's count in a rehearsal only."""
    t0 = time.monotonic()
    n = shards or config["shards"]
    workers = max(1, min(16, n, (os.cpu_count() or 2) - 1))
    if os.path.isdir(data_dir):
        shutil.rmtree(data_dir)
    for f in config["fields"]:
        fdir = os.path.join(data_dir, config["index"], f["name"])
        os.makedirs(fdir)
        meta = kind_of(f).meta(f)
        if meta is not None:
            with open(os.path.join(fdir, ".meta"), "w") as fh:
                json.dump(meta, fh)
    per_shard: list = [None] * n
    bits = 0
    # spawn, not fork: fresh interpreters, nothing inherited but the arguments
    with ProcessPoolExecutor(max_workers=workers, mp_context=get_context("spawn")) as pool:
        for fut in [pool.submit(build_shard, config, seed, s, data_dir) for s in range(n)]:
            s, data, nbits = fut.result()
            per_shard[s] = data
            bits += nbits
    disk = sum(
        os.path.getsize(os.path.join(d, name))
        for d, _, names in os.walk(data_dir)
        for name in names
    )
    facts = {
        "seconds": round(time.monotonic() - t0, 1),
        "workers": workers,
        "shards": n,
        "bits": bits,
        "disk_bytes": disk,
    }
    return reference_of(config, per_shard), facts
