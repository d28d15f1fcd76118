"""Telemetry on the profiler's clock: program spans as host events of a
`jax.profiler` trace, `jit.*` counters from JAX's compile events, the
spans of each host pass of a check, and the disabled path (nothing
recorded, no listener, no JAX import)."""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from jepsen_tpu import telemetry


@pytest.fixture
def on():
    prior = telemetry.enabled()
    telemetry.enable(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.enable(prior)


def _host_events(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((e.name, e.start_ns, e.end_ns, i))
    return out


def test_span_is_a_host_event_of_the_profiler_trace(on, tmp_path):
    import jax
    import jax.numpy as jnp

    x = np.arange(8, dtype=np.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("test.outer"):
            with telemetry.span("test.inner"):
                jnp.sin(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    evs = {n: (a, b, line) for n, a, b, line in _host_events(path)
           if n in ("test.outer", "test.inner")}
    assert set(evs) == {"test.outer", "test.inner"}
    (oa, ob, ol), (ia, ib, il) = evs["test.outer"], evs["test.inner"]
    assert ol == il and oa <= ia <= ib <= ob and oa < ob
    # The registry records the spans as before.
    assert {"test.outer", "test.inner"} <= set(telemetry.summary()["spans"])


def _jit_counts():
    c = telemetry.summary()["counters"]
    return c.get("jit.compiles", 0) + c.get("jit.cache-hits", 0)


def test_a_fresh_jit_shape_counts_one_executable(on):
    import jax

    f = jax.jit(lambda v: v * 3 + 1)
    x = np.ones(5, dtype=np.int32)
    before = _jit_counts()
    f(x).block_until_ready()
    after_first = _jit_counts()
    f(x).block_until_ready()
    assert after_first - before == 1
    assert _jit_counts() == after_first


def test_a_persistent_cache_hit_is_not_a_compile(tmp_path):
    """An executable loaded from the persistent cache counts under
    `jit.cache-hits` alone, though JAX times the load as a backend
    compile.  In a child process: the cache is process-wide state."""
    code = f"""
import jax
import numpy as np
from jax.experimental.compilation_cache import compilation_cache as cc
from jepsen_tpu import telemetry
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
cc.reset_cache()
telemetry.enable(True)
x = np.arange(7, dtype=np.int32)
jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
first = dict(telemetry.summary()["counters"])
jax.clear_caches()
jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
second = telemetry.summary()["counters"]
assert first.get("jit.compiles") == 1 and "jit.cache-hits" not in first, first
assert second["jit.compiles"] == 1 and second["jit.cache-hits"] == 1, second
print("ok")
"""
    out = _child(code)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().endswith("ok")


def _child(code):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for k in ("JEPSEN_TELEMETRY", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=120)


def _keyed_history(n_keys, n_ops, bad_keys):
    from jepsen_tpu.history.core import history
    from jepsen_tpu.parallel.independent import kv
    from jepsen_tpu.utils.histgen import random_register_history

    ops = []
    for i in range(n_keys):
        h = random_register_history(n_ops, procs=4, info_rate=0.05,
                                    seed=i, bad=i in bad_keys)
        ops += [o.replace(value=kv(f"k{i}", o.value)) for o in h]
    return history(ops)


@pytest.mark.parametrize("shape", ["register", "independent"])
def test_analyze_records_a_span_per_host_pass(on, shape):
    from jepsen_tpu import core
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.parallel.independent import (
        IndependentChecker,
        clear_settle_memo,
    )
    from jepsen_tpu.utils.histgen import random_register_history

    checker = Linearizable(cas_register())
    if shape == "register":
        h = random_register_history(120, procs=4, info_rate=0.05, seed=3)
        want = {"ingest.pack", "wgl.screen", "wgl.witness",
                "wgl.plan.pass.device-ladder"}
    else:
        clear_settle_memo()
        checker = IndependentChecker(checker)
        h = _keyed_history(8, 40, bad_keys={2, 5})
        want = {"ingest.split", "ingest.pack", "wgl.stream",
                "wgl.stream.concat", "wgl.plan.pass.stream-witness",
                "wgl.plan.pass.refute-screen",
                "wgl.plan.pass.settle-exact"}
    res = core.analyze({"checker": checker}, h)
    assert res["valid"] is (shape == "register")
    spans = telemetry.summary()["spans"]
    assert want <= set(spans), sorted(spans)
    if shape == "independent":
        # One pack span around the per-key loop, not one per key (the
        # settle pass's own single-key checks add theirs).
        evs = telemetry.events_between(0, limit=10 ** 6)
        assert any(e["name"] == "ingest.pack"
                   and e.get("attrs") == {"keys": 8} for e in evs)


def test_telemetry_off_records_nothing_and_imports_no_jax():
    code = """
import sys
from jepsen_tpu import telemetry
assert "jax" not in sys.modules
telemetry.enable(False)
import jax
from jax._src import monitoring
import numpy as np
with telemetry.span("x.y"):
    jax.jit(lambda v: v + 1)(np.ones(3)).block_until_ready()
telemetry.count("x.z")
s = telemetry.summary()
assert s["spans"] == {} and s["counters"] == {}, s
assert telemetry._on_jax_event not in monitoring.get_event_listeners()
assert telemetry._on_jax_duration not in \\
    monitoring.get_event_duration_listeners()
assert telemetry._profiler is None
print("ok")
"""
    out = _child(code)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
