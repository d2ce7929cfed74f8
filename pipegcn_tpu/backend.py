"""Process-wide JAX backend plumbing shared by every entry point:
where the persistent compilation cache lives, what device the process
actually got, and the one check that refuses to measure on the wrong
one.

Nothing here falls back. `require_tpu` raises when the platform is not
a TPU (a measurement taken on the CPU backend is not a measurement of
this system), and the device line is printed by every entry point so a
run where JAX itself came up on the CPU is visible in the log.
"""

from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed on purpose: a path that moves (temp name, pid, timestamp) is
# never found again by the next process.
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


class WrongBackend(RuntimeError):
    """The process did not get the accelerator the caller requires."""


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that path. Call before the first compile.

    Where JAX_COMPILATION_CACHE_DIR is set JAX has already read it and
    nothing is set in code; otherwise the cache goes to a fixed
    directory inside the checkout (gitignored). JAX's own thresholds
    stay as they are: a program that compiles in under
    jax_persistent_cache_min_compile_time_secs (1 s) is not written,
    which costs a warm process at most that much per program."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def compiled_text_uncached(lowered) -> str:
    """Optimized HLO text of `lowered`, compiled past the persistent
    cache. The cache's key leaves instruction metadata out
    (`jax_compilation_cache_include_metadata_in_key` is off, and has to
    stay off: source lines are metadata), so an entry written by an
    older checkout serves its OWN `op_name` scopes; a text read from a
    cache hit names the scopes of whoever compiled the program first.
    The join from a trace to named scopes (obs/profiler.py,
    obs/anatomy.py) needs this checkout's names, and the instruction
    names are the same either way (same compiler, same program). Costs
    one real compile; nothing is written to the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # an option at its default value changes nothing in the program
        # but keys JAX's in-memory table of compiled lowerings apart: a
        # lowering whose executable came out of the persistent cache
        # would otherwise hand that same executable back
        return lowered.compile(
            compiler_options={"xla_dump_hlo_as_text": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def device_summary() -> dict:
    """The device as JAX reports it: platform, device_kind, count."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line() -> str:
    """One log line naming what this process runs on."""
    d = device_summary()
    return (f"devices: {d['count']} x {d['kind']} "
            f"(platform={d['platform']})")


def require_tpu(allow_cpu: bool = False) -> dict:
    """The shared device check of the measurement entry points
    (bench.py, chip_smoke.py, the measurement scripts): returns
    `device_summary()` when the platform is a TPU, raises WrongBackend
    otherwise. `allow_cpu` is the explicit --cpu opt-in for harness dry
    runs; the caller labels such a run as cpu and it carries no chip
    number."""
    d = device_summary()
    if d["platform"] == "tpu":
        return d
    if allow_cpu and d["platform"] == "cpu":
        return d
    raise WrongBackend(
        f"this entry point measures on a TPU, but JAX found "
        f"platform={d['platform']!r} ({d['count']} x {d['kind']}); "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}. "
        f"There is no fallback: run it on the chip.")


def start_measurement(cpu: bool = False) -> dict:
    """What every measurement entry point does between `import jax` and
    its first compile: honour an explicit --cpu dry run, place the
    compile cache, refuse the wrong device, and say on stderr what it
    got. Returns `device_summary()`."""
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    place_compile_cache()
    dev = require_tpu(allow_cpu=cpu)
    print(f"# {device_line()}", file=sys.stderr)
    return dev
