"""pipegcn_tpu/backend.py (compile-cache placement, the device check),
the peaks table, and the source-hashed native library name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from pipegcn_tpu import backend
n = {"hits": 0, "misses": 0}
def on_event(name, **kw):
    if name.endswith("/cache_hits"): n["hits"] += 1
    if name.endswith("/cache_misses"): n["misses"] += 1
jax.monitoring.register_event_listener(on_event)
before = jax.config.jax_compilation_cache_dir
placed = backend.place_compile_cache()
if "--compile" in sys.argv:
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"before": before, "placed": placed,
                  "config": jax.config.jax_compilation_cache_dir, **n}))
"""


def _probe(*argv, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_ENABLE_COMPILATION_CACHE")}
    full.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, "-c", _PROBE, *argv], env=full,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_unset_uses_the_fixed_in_checkout_path():
    from pipegcn_tpu import backend

    assert backend.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    got = _probe()
    assert got["before"] is None
    assert got["placed"] == got["config"] == backend.DEFAULT_CACHE_DIR


def test_compile_cache_env_set_code_sets_nothing_and_second_run_hits(
        tmp_path):
    where = str(tmp_path / "cc")
    env = dict(JAX_COMPILATION_CACHE_DIR=where,
               # the probe's program compiles in well under JAX's 1 s
               # threshold; the threshold is JAX's knob, not the helper's
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    first = _probe("--compile", **env)
    # JAX read the variable itself; the helper changed nothing
    assert first["before"] == first["placed"] == first["config"] == where
    assert first["hits"] == 0 and first["misses"] > 0
    assert os.listdir(where)
    second = _probe("--compile", **env)
    assert second["hits"] > 0 and second["misses"] == 0


_SCOPE_PROBE = """
import json, re, sys
import jax, jax.numpy as jnp
from pipegcn_tpu import backend
backend.place_compile_cache()
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) @ x.T
jf = jax.jit(f)
x = jnp.ones((64, 64))
jf(x).block_until_ready()          # as a run dispatches before its window
low = jf.lower(x)
def scopes(text):
    return sorted(set(re.findall(r'op_name="jit\\(f\\)/(\\w+)/', text)))
n = {"hits": 0}
def on_event(name, **kw):
    if name.endswith("/cache_hits"): n["hits"] += 1
jax.monitoring.register_event_listener(on_event)
print(json.dumps({"cached": scopes(low.compile().as_text()),
                  "uncached": scopes(backend.compiled_text_uncached(low)),
                  "hits_after": n["hits"],
                  "still_on": jax.config.jax_enable_compilation_cache}))
"""


def test_a_cached_executable_names_the_scopes_of_who_compiled_it(tmp_path):
    """The cache's key leaves metadata out, so a second checkout that
    renamed a scope is served the first one's names: the text for the
    scope join is compiled past the cache, and the cache stays on."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_ENABLE_COMPILATION_CACHE"}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")

    def run(scope):
        r = subprocess.run([sys.executable, "-c", _SCOPE_PROBE, scope],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first = run("before")
    assert first["cached"] == first["uncached"] == ["before"]
    second = run("after")
    assert second["cached"] == ["before"]       # stale: the first's names
    assert second["uncached"] == ["after"]
    assert second["still_on"] is True


def test_require_tpu_refuses_the_cpu_and_names_it():
    from pipegcn_tpu import backend

    with pytest.raises(backend.WrongBackend, match="platform='cpu'"):
        backend.require_tpu()
    dev = backend.require_tpu(allow_cpu=True)
    assert dev == backend.device_summary()
    assert dev["platform"] == "cpu" and dev["count"] == 8
    assert backend.device_line() == "devices: 8 x cpu (platform=cpu)"


def test_peaks_table_knows_v5e_and_raises_on_anything_else():
    from pipegcn_tpu.obs import hw

    v5e = hw.peaks_for("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bytes_s == 819e9
    assert v5e.source
    for kind in ("TPU v5", "TPU v5p", "cpu", ""):
        with pytest.raises(hw.UnknownDevice, match="no published peaks"):
            hw.peak_flops_for(kind)


def test_native_library_name_follows_its_sources(tmp_path, monkeypatch):
    from pipegcn_tpu import native

    assert native.available()
    # the library this process loaded is the one named after the sources
    assert native.status().endswith(native.lib_name())
    src_dir = os.path.dirname(native.__file__)
    for s in native._SOURCES:
        shutil.copy(os.path.join(src_dir, s), tmp_path / s)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    assert native.lib_name() == os.path.basename(native.status())
    with open(tmp_path / native._SOURCES[0], "a") as f:
        f.write("\n// edited\n")
    assert native.lib_name() != os.path.basename(native.status())
