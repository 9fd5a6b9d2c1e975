"""CPU rehearsal of chip_smoke.py (guide on-chip-measurement §2,
rehearsals 1 and 2): the whole script at a tiny size against a real
``python -m pilosa_tpu server`` child on the CPU back end, steered from
here and not through options of the program.

The script must fail without a TPU, so the rehearsal patches the
platform it demands; everything else runs as it would on the chip. A
CPU reports no ``memory_stats()``, so the checks that read device bytes
are the ones a rehearsal cannot pass.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = [
    "--shards", "2",
    "--hot-rows", "256",
    "--hot-bits", "3000",
    "--tail-rows", "2000",
]


def _rehearse(mp, tmp, devices: int, extra=()):
    """Patch what only a chip can satisfy and return the argument list
    for a tiny run whose server child sees ``devices`` CPU devices."""
    mp.setattr(chip_smoke, "PLATFORM", "cpu")
    mp.setattr(chip_smoke, "MIN_RESIDENT_BYTES", 0)
    mp.setattr(chip_smoke, "OUT_DIR", str(tmp / "out"))
    # the script refuses to spawn from a process that imported JAX (it
    # would hold the chip); this worker's JAX is held to the CPU, and
    # nothing imports it again before the patch is undone
    mp.delitem(sys.modules, "jax", raising=False)
    mp.setenv("JAX_PLATFORMS", "cpu")
    # a cache directory of its own: other test workers write the shared
    # one while the script counts entries
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "jax_cache"))
    mp.setenv("XLA_FLAGS", f"--xla_force_host_platform_device_count={devices}")
    return chip_smoke.parse_args([*TINY, "--data-dir", str(tmp / "data"), *extra])


@pytest.fixture(scope="module")
def one_chip_obs(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        args = _rehearse(mp, tmp_path_factory.mktemp("smoke1"), devices=1)
        return chip_smoke.collect(args)


def test_rehearsal_proves_the_device_path(one_chip_obs, capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "MIN_RESIDENT_BYTES", 0)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    assert chip_smoke.problems(one_chip_obs) == []
    assert chip_smoke.finish(one_chip_obs) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # every read family launched on the device in both passes, the warm
    # pass compiled nothing, and the Set was read back
    assert set(one_chip_obs["cold"]) == set(one_chip_obs["warm"]) == {
        "topn", "chain", "bsi_sum", "fused", "bsi_range", "groupby", "bitmap",
    }
    assert one_chip_obs["warm_compiles"] == 0
    # a second run sharing the cache directory reads this one's record
    assert chip_smoke._previous_run(one_chip_obs["cache_dir"], 1)["chips"] == 1


@pytest.mark.parametrize(
    "counter",
    [name.replace(".", "_") for name in chip_smoke.FALLBACK_COUNTERS],
)
def test_nonzero_fallback_counter_fails_the_run(one_chip_obs, counter, capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "MIN_RESIDENT_BYTES", 0)
    obs = copy.deepcopy(one_chip_obs)
    obs["metrics"].append((counter, {"call": "Count"}, 1.0))
    assert chip_smoke.finish(obs) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert counter.split("_")[-1] in err


@pytest.mark.parametrize(
    "mutate, reason",
    [
        (lambda o: o["build_info"].update(backend="cpu"), "backend"),
        (lambda o: o.update(server_exit_code=1), "exited with code 1"),
        (lambda o: o.update(warm_compiles=1.0), "warm pass compiled"),
        (lambda o: o.update(warm_cache_entries_added=2), "warm pass compiled"),
        (lambda o: o["build_info"].update(native="false"), "native"),
        (
            lambda o: o["metrics"].append(("fusion_bypasses", {"reason": "error"}, 1.0)),
            "fusion.bypasses",
        ),
    ],
    ids=["not-tpu", "child-exit", "warm-compile", "warm-cache-entry", "no-native", "fusion-error"],
)
def test_each_disproof_fails_the_run(one_chip_obs, mutate, reason, capsys):
    # PLATFORM stays "tpu" only for the back-end case
    with pytest.MonkeyPatch.context() as mp:
        if reason != "backend":
            mp.setattr(chip_smoke, "PLATFORM", "cpu")
        mp.setattr(chip_smoke, "MIN_RESIDENT_BYTES", 0)
        obs = copy.deepcopy(one_chip_obs)
        mutate(obs)
        assert chip_smoke.finish(obs) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out and reason in err


def test_resident_bytes_floor_needs_a_device_that_reports_them(one_chip_obs, monkeypatch):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    why = chip_smoke.problems(one_chip_obs)
    assert len(why) == 1 and "device memory in use" in why[0]


def test_planted_wrong_answer_exits_nonzero(tmp_path, capsys):
    with pytest.MonkeyPatch.context() as mp:
        _rehearse(mp, tmp_path, devices=1)
        honest = chip_smoke.Reference.count
        mp.setattr(
            chip_smoke.Reference, "count", lambda self, e: honest(self, e) + 1
        )
        rc = chip_smoke.main([*TINY, "--data-dir", str(tmp_path / "data")])
    out, err = capsys.readouterr()
    assert rc == 1
    assert '"ok"' not in out
    assert "answer differs from the numpy reference" in err


def test_mesh_phase_rehearsal_on_four_virtual_devices(tmp_path, capsys):
    with pytest.MonkeyPatch.context() as mp:
        args = _rehearse(mp, tmp_path, devices=4, extra=["--chips", "4"])
        obs = chip_smoke.collect(args)
        # only the mesh phase and what it is compared with
        assert set(obs["cold"]) == {"topn", "chain", "bsi_sum"}
        assert obs["build_info"]["device_count"] == "4"
        # all a CPU cannot show is where the bytes live
        assert chip_smoke.problems(obs) == [
            "memory_stats() reported for 0 devices, not 4"
        ]
        # with per-device bytes reported, a lopsided placement fails
        obs["hbm_after_warm"] = {
            f"tpu:{dev}": float(nbytes)
            for dev, nbytes in enumerate((4 << 30, 1 << 20, 1 << 20, 1 << 20))
        }
        (why,) = chip_smoke.problems(obs)
        assert "not spread over the devices" in why


def test_refuses_to_run_with_jax_held_to_the_cpu(tmp_path):
    """As the driver runs it in a sandbox: no options, JAX_PLATFORMS=cpu.
    It must fail at once and print no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "JAX_PLATFORMS=cpu" in proc.stderr
