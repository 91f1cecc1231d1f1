"""Test fixtures.

Distribution is simulated on a virtual 8-device CPU mesh — the TPU
equivalent of the reference's ``local[4]`` Spark test sessions
(``SparkInvolvedSuite.scala:31-47``): set XLA_FLAGS before JAX import so
``jax.devices()`` reports 8 host devices.
"""

import os

# Must happen before any jax import anywhere in the test process: tests
# run on the CPU and simulate the mesh with 8 virtual host devices. The
# chip is exercised by chip_smoke.py, never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_session():
    """When ``HS_LOCK_WITNESS=<path>`` is set, wrap every
    SHARED_STATE-registered lock for the whole test session and dump
    the observed acquisition edges + per-lock counts into the artifact
    at exit (merging across suites). ``hslint --witness <path>`` then
    cross-checks the runtime behavior against the static lock model —
    see scripts/bench_smoke.sh, docs/static-analysis.md."""
    path = os.environ.get("HS_LOCK_WITNESS")
    if not path:
        yield
        return
    from hyperspace_tpu.testing import lock_witness

    lock_witness.install()
    try:
        yield
    finally:
        lock_witness.dump(path)
        lock_witness.uninstall()


@pytest.fixture(scope="session", autouse=True)
def _residency_witness_session():
    """When ``HS_RESIDENCY_WITNESS=<path>`` is set, wrap every
    ALLOC_SITES-registered allocation site for the whole test session
    and dump the observed per-site peak bytes + call counts + process
    RSS high-water into the artifact at exit (merging across suites).
    ``hslint --witness <path>`` then cross-checks the runtime residency
    against the static bound model — see scripts/bench_smoke.sh,
    docs/static-analysis.md."""
    path = os.environ.get("HS_RESIDENCY_WITNESS")
    if not path:
        yield
        return
    from hyperspace_tpu.testing import residency_witness

    residency_witness.install()
    try:
        yield
    finally:
        residency_witness.dump(path)
        residency_witness.uninstall()


@pytest.fixture
def tmp_index_root(tmp_path):
    """Per-test index system path (HyperspaceSuite's per-suite systemPath)."""
    p = tmp_path / "indexes"
    p.mkdir()
    return str(p)


@pytest.fixture
def sample_parquet(tmp_path):
    """Small parquet dataset (reference SampleData.scala analogue)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(0)
    d = tmp_path / "sample"
    d.mkdir()
    for i in range(3):
        n = 100
        t = pa.table(
            {
                "date": pa.array(
                    [f"2017-09-{(j % 28) + 1:02d}" for j in range(n)]
                ),
                "rguid": pa.array([f"guid-{i}-{j}" for j in range(n)]),
                "clicks": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
                "query": pa.array(
                    [["ibraco", "facebook", "donde", "banana"][j % 4] for j in range(n)]
                ),
                "imprs": pa.array(rng.integers(0, 100, n), type=pa.int64()),
            }
        )
        pq.write_table(t, d / f"part-{i}.parquet")
    return str(d)


def _make_session(index_root, n_devices=None):
    from hyperspace_tpu.session import HyperspaceSession
    from hyperspace_tpu import constants as C

    devices = jax.devices()[:n_devices] if n_devices is not None else None
    s = HyperspaceSession(devices=devices)
    s.conf.set(C.INDEX_SYSTEM_PATH, index_root)
    # Small bucket count for tests (reference tests use 5 shuffle partitions)
    s.conf.set(C.INDEX_NUM_BUCKETS, 8)
    return s


@pytest.fixture(params=[1, 8], ids=["mesh1", "mesh8"])
def session(request, tmp_index_root):
    """Every session-driven test runs at mesh sizes 1 and 8 — the
    HybridScanSuite-style matrix (the reference specializes shared
    scenarios per environment; here the environment axis is the mesh)."""
    return _make_session(tmp_index_root, request.param)


@pytest.fixture
def session_factory(tmp_index_root):
    """Build sessions of chosen mesh size over the SAME index system path
    (cross-mesh layout-compat tests: build at one size, serve at another)."""
    return lambda n_devices: _make_session(tmp_index_root, n_devices)
