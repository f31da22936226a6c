import os

import numpy as np
import pytest

# Small default workload sizes keep the tier-1 suite fast (<~60 s);
# heavyweight end-to-end sweeps carry @pytest.mark.slow and run via
# `pytest -m slow`.
SMALL_ROWS = 4000
SMALL_COLS = 4
SMALL_TXNS = 8000
SMALL_QUERIES = 12


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def small_workload():
    """(table, stream, queries) HTAP microbenchmark at small default sizes."""
    from repro.core import engine, schema

    rng = np.random.default_rng(0)
    sch = schema.make_schema("t", SMALL_COLS, 32)
    table = schema.gen_table(rng, sch, SMALL_ROWS)
    stream = schema.gen_update_stream(rng, sch, SMALL_ROWS, SMALL_TXNS,
                                      write_ratio=0.5)
    queries = engine.gen_queries(rng, SMALL_QUERIES, SMALL_COLS)
    return table, stream, queries


# Devices the island-mesh tests lay one analytical island on each of.
HOST_DEVICES = 4
# module path -> {test name: None where it passed in the child, else why}
_host_device_runs: dict = {}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", f"host_devices: the test needs {HOST_DEVICES} devices; "
        "a process with fewer runs it in a child process that has them")


def _run_with_host_devices(item) -> dict:
    """The marked tests of ``item``'s module, run once in a child process
    with ``HOST_DEVICES`` forced host devices (XLA reads the flag before
    JAX starts, so this process cannot have them): test name -> None
    where it passed there, else its report."""
    import subprocess
    import sys
    import tempfile
    import xml.etree.ElementTree as ET

    root = item.config.rootpath
    ids = [i.nodeid for i in item.session.items
           if i.path == item.path and i.get_closest_marker("host_devices")]
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               [str(root / "src"), str(root)]
               + [p for p in [os.environ.get("PYTHONPATH")] if p]),
           "XLA_FLAGS": " ".join(
               [os.environ.get("XLA_FLAGS", ""),
                f"--xla_force_host_platform_device_count={HOST_DEVICES}"]
           ).strip()}
    with tempfile.TemporaryDirectory() as tmp:
        xml = os.path.join(tmp, "junit.xml")
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "no:randomly", f"--junitxml={xml}", *ids],
            cwd=root, env=env, capture_output=True, text=True,
            timeout=1200)
        if not os.path.exists(xml):
            raise RuntimeError(f"the child run left no report:\n"
                               f"{out.stdout[-3000:]}\n{out.stderr[-3000:]}")
        cases = list(ET.parse(xml).iter("testcase"))
    outcomes = {}
    for case in cases:
        bad = [e for e in case if e.tag in ("failure", "error", "skipped")]
        outcomes[case.get("name")] = (
            None if not bad else (f"{bad[0].tag}: {bad[0].get('message')}\n"
                                  f"{bad[0].text or ''}")[-3000:])
    return outcomes


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """A test marked ``host_devices``, in a process with fewer devices,
    passes or fails as its run in the child process did."""
    if not pyfuncitem.get_closest_marker("host_devices"):
        return None
    import jax

    if jax.device_count() >= HOST_DEVICES:
        return None
    key = str(pyfuncitem.path)
    if key not in _host_device_runs:
        _host_device_runs[key] = _run_with_host_devices(pyfuncitem)
    outcomes = _host_device_runs[key]
    name = pyfuncitem.name
    assert name in outcomes, f"{name} did not run with host devices"
    assert outcomes[name] is None, outcomes[name]
    return True


@pytest.fixture
def process_state(monkeypatch):
    """Give back what opening a cell changes for the whole process: it
    drops every ``REPRO_*`` variable and turns JAX's persistent compile
    cache on for every compile."""
    import jax
    from jax._src import compilation_cache

    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        monkeypatch.setenv(k, os.environ[k])   # put back at teardown
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
