"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases — driven directly at tiny sizes with interpret-mode kernels — pass
their own oracle checks; plus the compilation-cache placement helper."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.compile_cache import CHECKOUT_CACHE, place_compilation_cache
from repro.sparse.generators import rmat_csr, stencil2d_csr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke_mod():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(smoke_mod):
    return smoke_mod.Smoke(seed=3, interpret=True)


def test_smoke_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_alone_exits_nonzero(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SCRIPT).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(lone)], capture_output=True,
                          text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("make", [
    lambda: stencil2d_csr(12, 10),
    lambda: rmat_csr(7, 16, seed=5),
], ids=["stencil", "rmat"])
def test_deployment_phase_passes_oracle(smoke_mod, smoke, make, capsys):
    a = make()
    line = smoke.phase("deployment:tiny", smoke_mod.deployment_phase,
                       "tiny", a, a)
    assert line["m"] == a.m and line["nnz_c"] > 0
    assert line["f_m"] <= line["fm_cap"]
    assert line["rows_checked"] >= smoke_mod.HEAVY_ROWS
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["phase"] == "deployment:tiny"


def test_served_phase_passes_oracle(smoke_mod, smoke):
    structures = [(rmat_csr(6, 8, seed=s),) * 2 for s in (1, 2)]
    line = smoke.phase("served", smoke_mod.served_phase, structures)
    assert line["completed"] == 8 and line["structures"] == 2


def test_kernel_phase_runs_every_kernel(smoke_mod, smoke):
    a = rmat_csr(5, 8, seed=2)
    line = smoke.phase("kernels", smoke_mod.kernel_phase, "tiny", a, a)
    assert line["kernels"] == ["symbolic", "dense_acc", "flat_lp", "pallas",
                               "pallas_lp"]
    assert "kernel_sources" not in line


def test_check_rows_catches_a_wrong_value(smoke_mod):
    from repro.core.spgemm import spgemm

    a = stencil2d_csr(6, 6)
    res = spgemm(a, a)
    host = smoke_mod.host_csr(a)
    rows = np.arange(a.m)
    ip = np.asarray(res.c.indptr)
    smoke_mod.check_rows("ok", host, host, host[2], host[2], ip,
                         res.c.indices, res.c.values, rows)
    bad = res.c.values.at[int(ip[7])].add(1e-2)
    with pytest.raises(smoke_mod.SmokeFailure, match="value"):
        smoke_mod.check_rows("bad", host, host, host[2], host[2], ip,
                             res.c.indices, bad, rows)


def test_phase_fails_on_a_fallback(smoke_mod, smoke):
    from repro.core.telemetry import FALLBACK_COUNTS

    def degraded(_smoke):
        FALLBACK_COUNTS["fault:pallas->xla"] += 1
        return {}

    with pytest.raises(smoke_mod.SmokeFailure, match="fallbacks"):
        smoke.phase("degraded", degraded)


def test_sharded_phase_on_four_host_devices():
    """The --chips 4 phase on 4 virtual CPU devices (subprocess: the
    device-count flag must precede jax initialization)."""
    body = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        smoke_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke_mod)
        import logging
        import tempfile
        import jax
        from repro.launch.mesh import make_data_mesh
        from repro.sparse.generators import rmat_csr
        jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        a = rmat_csr(7, 8, seed=1)
        mesh = make_data_mesh(4)
        # single-device + both placements
        assert smoke_mod.warm_expansions([(a, a)], mesh) == 3
        misses = []

        class Misses(logging.Handler):
            def emit(self, record):
                if "CACHE MISS" in record.getMessage():
                    misses.append(record.getMessage())

        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.addHandler(Misses())
        smoke = smoke_mod.Smoke(seed=0, interpret=True)
        line = smoke.phase("sharded", smoke_mod.sharded_phase, "tiny", a, a,
                           mesh)
        print(sorted(line["placements"]))
        print("misses", len(misses))
        print("expand misses", sum("expand" in m for m in misses))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "['allgather', 'replicated']" in proc.stdout
    # the phase compiled its other programs, but no expansion: the warmed
    # ones (single-device, replicated, allgather) were found, not rebuilt
    assert int(proc.stdout.split("\nmisses")[-1].split()[0]) > 0
    assert proc.stdout.rstrip().endswith("expand misses 0")


@pytest.fixture
def cache_dir_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_helper_keeps_a_configured_dir(cache_dir_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert place_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_helper_defaults_to_checkout(cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    assert place_compilation_cache() == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE == __import__("pathlib").Path(REPO) / ".jax_cache"


def test_warm_expansions_feed_the_persistent_cache(smoke_mod, tmp_path,
                                                   cache_dir_config):
    """The warm-up's ahead-of-time compiles are the very programs spgemm
    dispatches: after warming, the expansion loads from the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core.spgemm import spgemm

    events = []
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    listener = lambda event, **_: events.append(event)  # noqa: E731
    jax.monitoring.register_event_listener(listener)
    try:
        a = rmat_csr(6, 8, seed=11)
        assert smoke_mod.warm_expansions([(a, a), (a, a)]) == 1
        events.clear()
        spgemm(a, a, method="sparse", plan_cache=False)
        assert "/jax/compilation_cache/cache_hits" in events
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_listener(listener)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
