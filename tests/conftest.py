"""Test configuration: an 8-device virtual CPU mesh.

The suite runs on the CPU back end whatever the machine holds, set
*before* any jax backend initialisation. Multi-chip shardings are
validated on 8 virtual CPU devices. What only the TPU's compiler can say
is asked in tests/test_tpu_compile.py; what only a chip can say, by
chip_smoke.py.
"""

from pilosa_tpu.utils.jaxplatform import force_cpu_mesh

force_cpu_mesh(8)


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the soak rides outside it
    config.addinivalue_line(
        "markers", "slow: long multi-process soaks excluded from tier-1"
    )
