"""Two-process jax.distributed mesh dryrun — the cross-HOST collective
plane (VERDICT r4 missing #2).

The reference's cluster spans machines as first-class (reference
cluster.go:788-857, memberlist gossip across hosts); the rebuild's SPMD
mesh equivalents (parallel/spmd.py) had only ever run in a single
process. This dryrun initializes a REAL multi-process JAX runtime —
``jax.distributed.initialize`` with a coordinator, N processes, each
owning a slice of the global device set — and runs every serving
collective (psum for Count/Sum, all_gather for TopN) over a mesh whose
shard axis SPANS the process boundary, exactly how a multi-host TPU
deployment lays pods over DCN.

Parent mode spawns the workers and aggregates their per-op verdicts:

    python dryrun_multiprocess.py            # 2 processes x 4 devices
    python dryrun_multiprocess.py --procs 2 --devices-per-proc 4

Worker mode (spawned): PILOSA_MP_RANK set.
"""

from __future__ import annotations

import json
import os
import sys

COORD_PORT_ENV = "PILOSA_MP_COORD"
RANK_ENV = "PILOSA_MP_RANK"
NPROCS_ENV = "PILOSA_MP_NPROCS"
DEVS_ENV = "PILOSA_MP_DEVS"


def worker() -> None:
    rank = int(os.environ[RANK_ENV])
    nprocs = int(os.environ[NPROCS_ENV])
    devs = int(os.environ[DEVS_ENV])

    import jax

    # cross-process collectives on the CPU backend need an explicit
    # implementation: without gloo selected, XLA raises "Multiprocess
    # computations aren't implemented on the CPU backend" at dispatch.
    # Guarded: the flag name is version-dependent and irrelevant on
    # real multi-host TPU (ICI/DCN collectives need no selection).
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{os.environ[COORD_PORT_ENV]}",
        num_processes=nprocs,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel.spmd import (
        SHARD_AXIS,
        bsi_sum_spmd,
        count_fold_spmd,
        make_mesh,
        topn_spmd,
    )

    assert jax.process_count() == nprocs, jax.process_count()
    devices = jax.devices()  # GLOBAL: nprocs * devs
    assert len(devices) == nprocs * devs, len(devices)
    mesh = make_mesh(devices)
    sharding = NamedSharding(mesh, P(SHARD_AXIS))

    S, K, R, D, W = len(devices), 3, 8, 4, 64
    rng = np.random.default_rng(0)  # same seed every process: shared oracle
    rows = rng.integers(0, 2**32, size=(S, K, W), dtype=np.uint32)
    src = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
    mat = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
    planes = rng.integers(0, 2**32, size=(S, D + 1, W), dtype=np.uint32)
    filt = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)

    def put(arr):
        # each process contributes its LOCAL slice of the global array
        # (multi-host device_put requires addressable data only): with
        # the 1-D shard axis over jax.devices() (process-major order),
        # rank r owns rows [r*devs, (r+1)*devs)
        local = arr[rank * devs : (rank + 1) * devs]
        return jax.make_array_from_process_local_data(
            sharding, local, global_shape=arr.shape
        )

    ok: dict[str, bool] = {}

    # Count — psum over a shard axis that crosses the process boundary
    count = int(count_fold_spmd(mesh)(put(rows)))
    want = sum(
        int(np.bitwise_count(np.bitwise_and.reduce(rows[s], axis=0)).sum())
        for s in range(S)
    )
    ok["count_psum"] = count == want

    # TopN — local top-k + all_gather across processes
    ids, counts = topn_spmd(mesh, 4)(put(src), put(mat))
    # replicated output: every process holds all S*k candidates locally
    local_ids = np.asarray(ids.addressable_shards[0].data)
    ok["topn_all_gather"] = local_ids.shape[-1] == S * 4

    # BSI Sum — per-plane popcounts psum'd across processes
    plane_counts = np.asarray(
        bsi_sum_spmd(mesh, D, ("leaf", 0))(put(planes), put(filt)).addressable_shards[0].data
    )
    want_planes = np.array(
        [
            sum(
                int(
                    np.bitwise_count(
                        np.bitwise_and(planes[s, d], filt[s])
                    ).sum()
                )
                for s in range(S)
            )
            for d in range(D + 1)
        ]
    )
    ok["bsi_sum_psum"] = bool((plane_counts == want_planes).all())

    print(
        json.dumps(
            {
                "rank": rank,
                "process_count": jax.process_count(),
                "global_devices": len(devices),
                "local_devices": jax.local_device_count(),
                "ok": ok,
            }
        ),
        flush=True,
    )
    sys.exit(0 if all(ok.values()) else 1)


def parent(nprocs: int, devs: int) -> int:
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devs}",
        **{COORD_PORT_ENV: str(port), NPROCS_ENV: str(nprocs), DEVS_ENV: str(devs)},
    )
    procs = []
    for rank in range(nprocs):
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                env={**env, RANK_ENV: str(rank)},
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    results, rc = [], 0
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            rc = 1
        for line in out.splitlines():
            if line.startswith("{"):
                results.append(json.loads(line))
        if p.returncode != 0:
            rc = 1
            print(f"rank {rank} exited {p.returncode}\n{err[-2000:]}", file=sys.stderr)
    summary = {
        "what": (
            "2-process jax.distributed CPU mesh dryrun: every serving "
            "collective (count psum, TopN all_gather, BSI Sum psum) over "
            "a shard axis spanning the process boundary — the cross-host "
            "plane of a multi-host TPU deployment (reference "
            "cluster.go:788-857 spans machines via gossip+HTTP)"
        ),
        "processes": nprocs,
        "devices_per_process": devs,
        "ok": rc == 0 and len(results) == nprocs,
        "per_rank": results,
    }
    print(json.dumps(summary, indent=2))
    with open(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "MULTIPROCESS_r5.json"),
        "w",
    ) as f:
        json.dump(summary, f, indent=2)
    return rc


if __name__ == "__main__":
    if os.environ.get(RANK_ENV) is not None:
        worker()
    else:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--procs", type=int, default=2)
        ap.add_argument("--devices-per-proc", type=int, default=4)
        a = ap.parse_args()
        sys.exit(parent(a.procs, a.devices_per_proc))
