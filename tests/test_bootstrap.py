"""Process bootstrap (utils/jaxplatform.py): where the compilation cache
goes, and that the JAX-free parts stay JAX-free. Each case runs in a
fresh interpreter: the cache directory is process-global JAX state."""

import json
import os
import subprocess
import sys

import pytest

from pilosa_tpu.utils.jaxplatform import DEFAULT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, tmp_path, **env) -> dict:
    """Run ``code`` in a fresh interpreter with a HOME of its own; it
    prints one JSON object. Unset variables are passed as None."""
    full = {**os.environ, "HOME": str(tmp_path), "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    for k, v in env.items():
        if v is None:
            full.pop(k, None)
        else:
            full[k] = v
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=full,
        cwd=str(tmp_path),
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_RESOLVE = """
import json, os, sys
{first}
from pilosa_tpu.utils.jaxplatform import bootstrap
returned = bootstrap()
import jax
print(json.dumps({{
    "returned": returned,
    "jax": jax.config.jax_compilation_cache_dir,
    "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    "floor": jax.config.jax_persistent_cache_min_compile_time_secs,
    "home_cache": os.path.exists(os.path.expanduser("~/.cache")),
}}))
"""


@pytest.mark.parametrize("first", ["", "import jax"], ids=["before-jax", "after-jax"])
def test_cache_dir_from_the_environment_and_no_other(tmp_path, first):
    want = str(tmp_path / "elsewhere")
    got = _run(_RESOLVE.format(first=first), tmp_path, JAX_COMPILATION_CACHE_DIR=want)
    assert got["returned"] == got["jax"] == got["env"] == want
    # JAX creates it on first write; the program made no directory
    assert not os.path.exists(want) and not got["home_cache"]


@pytest.mark.parametrize("first", ["", "import jax"], ids=["before-jax", "after-jax"])
def test_cache_dir_unset_is_the_fixed_path_in_the_checkout(tmp_path, first):
    got = _run(_RESOLVE.format(first=first), tmp_path)
    assert got["returned"] == got["jax"] == got["env"] == DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert got["floor"] == 0 and not got["home_cache"]


def test_jax_own_switch_turns_the_cache_off(tmp_path):
    code = (
        "import json; from pilosa_tpu.utils.jaxplatform import bootstrap; bootstrap();"
        "import jax; print(json.dumps({'on': jax.config.jax_enable_compilation_cache}))"
    )
    assert _run(code, tmp_path, JAX_ENABLE_COMPILATION_CACHE="0") == {"on": False}


_SERVER = """
import json, os, subprocess, sys
from pilosa_tpu.server.config import Config
from pilosa_tpu.server.server import Server
Server(Config(data_dir=os.path.join(os.getcwd(), "d"), bind="127.0.0.1:0"))
import jax
child = subprocess.run(
    [sys.executable, "-c", "import jax; print(jax.config.jax_compilation_cache_dir)"],
    capture_output=True, text=True, check=True,
).stdout.strip()
print(json.dumps({"server": jax.config.jax_compilation_cache_dir, "child": child}))
"""

_CLI = """
import contextlib, io, json, os, sys
from pilosa_tpu.cli.main import main
open("ok.py", "w").write("x = 1")
with contextlib.redirect_stdout(io.StringIO()):
    assert main(["check", "--strict", "ok.py"]) == 0
print(json.dumps({"cli": os.environ["JAX_COMPILATION_CACHE_DIR"],
                  "jax_imported": "jax" in sys.modules}))
"""


def test_server_cli_and_spawned_child_resolve_the_same_directory(tmp_path):
    server = _run(_SERVER, tmp_path)
    cli = _run(_CLI, tmp_path)
    assert server["server"] == server["child"] == cli["cli"] == DEFAULT_CACHE_DIR
    # the CLI's bootstrap only exports the directory: `check` runs
    # where no jax is installed (the CI job)
    assert cli["jax_imported"] is False


_NO_JAX = """
import json, sys
sys.path.insert(0, {repo!r})
import pilosa_tpu.roaring
import chip_smoke
spec = chip_smoke.Spec(seed=1, shards=1, hot_rows=16, hot_bits=200, tail_rows=50)
chip_smoke.build_shard(spec, 0, {data!r})
from pilosa_tpu import native_bridge
native_bridge.require()
print(json.dumps(sorted(m for m in sys.modules if m == "jax" or m.startswith(("jax.", "jaxlib")))))
"""


def test_roaring_and_the_smoke_data_builder_import_no_jax(tmp_path):
    data = str(tmp_path / "data")
    for sub in ("f/views/standard/fragments", "v/views/bsig_v/fragments"):
        os.makedirs(os.path.join(data, "smoke", sub))
    assert _run(_NO_JAX.format(repo=REPO, data=data), tmp_path) == []


def test_force_cpu_mesh_whether_or_not_jax_came_first(tmp_path):
    code = (
        "import json\n{first}\n"
        "from pilosa_tpu.utils.jaxplatform import force_cpu_mesh\n"
        "force_cpu_mesh(8)\nimport jax\n"
        "print(json.dumps([d.platform for d in jax.devices()]))"
    )
    for first in ("", "import jax"):
        got = _run(code.format(first=first), tmp_path, JAX_PLATFORMS=None, XLA_FLAGS=None)
        assert got == ["cpu"] * 8
