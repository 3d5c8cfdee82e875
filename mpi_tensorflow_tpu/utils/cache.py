"""Where the persistent compilation cache lives, plus the XLA:CPU safety
gates.

``enable_compile_cache()`` is THE way an entry point (cli.main, the
serving main, chip_smoke.py, tests/conftest.py) turns the persistent cache
on:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; the program
  uses that directory and sets no other in code — so whoever runs the
  program can place the cache where it survives;
- unset: ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, never a
  temp name, pid or timestamp: the path is part of what must stay put
  for a later process to hit.

XLA:CPU stores AOT-compiled executables keyed WITHOUT the full host
machine-feature set; loading an entry compiled on a different CPU type
warns "This could lead to execution errors such as SIGILL" — and does
exactly that, intermittently.  Two distinct hazards, both closed here
for forced-CPU runs:

1. FOREIGN entries (different box, same cache dir): closed by scoping
   the CPU cache under a fingerprint of the host's ISA *and model
   identity* (LLVM's -mcpu=native tuning differs between models whose
   /proc/cpuinfo flags are identical).
2. SAME-HOST reload: on some boxes LLVM's native tuning adds attributes
   (+prefer-no-gather/-scatter) that the AOT loader cannot verify
   against its host-feature probe, so the box cannot round-trip ITS OWN
   cache — every load warns "Machine type used for XLA:CPU compilation
   doesn't match", and a gather-heavy executable (the gspmd train step)
   aborted deterministically on reload.  ``cpu_cache_roundtrip_safe``
   detects this once per box with a compile-in-one-process /
   reload-in-another canary and persists the verdict; callers must
   leave the CPU cache OFF when it returns False.

The CPU scoping only ever picks a SUB-directory of the directory it was
given.  TPU entries are unaffected (device executables, loaded by the
runtime, not host-executed) and use the base directory.
"""

from __future__ import annotations

import hashlib
import os
import platform
import sys


def host_scoped_cpu_cache(base: str) -> str:
    """``base``/cpu-<isa fingerprint> — stable per machine type, and
    idempotent (an already-scoped path is returned unchanged, so every
    forced-CPU entry point can apply it unconditionally)."""
    try:
        with open("/proc/cpuinfo") as f:
            text = f.read()
        # x86 lists ISA extensions under "flags", aarch64 under
        # "Features".  The flags alone are NOT enough: LLVM's
        # -mcpu=native tuning attributes (+prefer-no-gather/-scatter,
        # set per CPU MODEL from CPUID family/model) vary between hosts
        # whose visible flag sets are identical — observed round 4 as a
        # cached AOT entry compiled with +prefer-no-gather crashing the
        # suite ("Fatal Python error") on a same-flags host without it.
        # So the fingerprint includes the model-identity lines too.
        # If none are present, fingerprint the whole file — a constant
        # fallback would let foreign AOT entries stay reachable, the
        # exact hazard this module exists to close.
        keys = ("flags", "Features", "model name", "model", "cpu family",
                "stepping", "vendor_id", "CPU implementer", "CPU part",
                "CPU variant")
        seen = {}
        for ln in text.splitlines():
            k = ln.split(":", 1)[0].strip()
            if k in keys and k not in seen:
                seen[k] = ln.strip()
        flags = "\n".join(seen[k] for k in keys if k in seen) or text
    except OSError:
        flags = platform.processor() or platform.machine()
    tag = hashlib.sha1(flags.encode()).hexdigest()[:12]
    if os.path.basename(os.path.normpath(base)) == f"cpu-{tag}":
        return base                      # already scoped
    path = os.path.join(base, f"cpu-{tag}")
    os.makedirs(path, exist_ok=True)
    return path


_CANARY = r"""
import os, sys
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def canary(x, idx):
    # a gather: the op class whose codegen the unverifiable
    # prefer-no-gather tuning attribute changes
    return jnp.take(x, idx, axis=0).sum() * 2.0

out = canary(jnp.arange(64.0).reshape(8, 8), jnp.array([1, 3, 5]))
print("CANARY_OK", float(out))
"""


_ROUNDTRIP_MEMO: dict = {}   # (isa tag, jaxlib ver) -> bool, per process


def _jaxlib_version() -> str:
    try:
        from importlib.metadata import version

        return version("jaxlib")
    except Exception:
        return "unknown"


def _persistent_probe(memo: dict, memo_key, verdict_path: str,
                      valid_verdicts, probe_fn):
    """THE shared probe-once contract for subprocess canaries: memoized
    per process (``memo[memo_key]`` holds the finished verdict string or
    None), persisted across processes at ``verdict_path``.  Invariants
    every caller gets from this one copy:

    - a persisted verdict in ``valid_verdicts`` short-circuits; a
      torn/garbage file (reader raced a non-atomic writer from an older
      version) falls through to a re-probe;
    - only a COMPLETED probe (``probe_fn`` returns a verdict string)
      publishes — an infrastructure failure (returns None) reports for
      this session only, so the next session retries;
    - publish is atomic (tmp + os.replace): a racing reader sees the old
      state or the full verdict, never a torn file.
    """
    if memo_key in memo:
        return memo[memo_key]
    if os.path.exists(verdict_path):
        try:
            with open(verdict_path) as f:
                content = f.read().strip()
        except OSError:
            content = ""
        if content in valid_verdicts:
            memo[memo_key] = content
            return content
    verdict = probe_fn()
    if verdict is not None:
        tmp = f"{verdict_path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(verdict)
            os.replace(tmp, verdict_path)
        except OSError:
            try:
                os.unlink(tmp)       # no stray tmp on ENOSPC/races
            except OSError:
                pass
    memo[memo_key] = verdict
    return verdict


def cpu_cache_roundtrip_safe(scoped_dir: str, timeout: int = 180) -> bool:
    """True when this box can reload its OWN XLA:CPU AOT cache entries.

    Compiles a small gather-containing jit in one subprocess (writing the
    entry into a throwaway dir), reloads it in a second, and checks the
    second's stderr for the AOT loader's machine-type mismatch warning —
    the signature of the same-host tuning-attribute hazard that aborted
    the round-4 suite.  The verdict persists next to the scoped dir,
    keyed by the jaxlib version (a loader upgrade re-probes), and is
    memoized per (ISA tag, version) in-process so multiple cache bases
    in one session pay ONE probe; canary-infrastructure failures report
    False without persisting (_persistent_probe contract)."""
    tag = os.path.basename(os.path.normpath(scoped_dir))
    ver = _jaxlib_version()

    def probe():
        import subprocess
        import tempfile

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"           # never inherits a chip
        cache = tempfile.mkdtemp(prefix="canary-", dir=os.path.dirname(
            os.path.normpath(scoped_dir)) or ".")
        try:
            r1 = subprocess.run([sys.executable, "-c", _CANARY, cache],
                                capture_output=True, text=True, env=env,
                                timeout=timeout)
            if r1.returncode == 0 and "CANARY_OK" in r1.stdout:
                r2 = subprocess.run([sys.executable, "-c", _CANARY, cache],
                                    capture_output=True, text=True,
                                    env=env, timeout=timeout)
                if r2.returncode == 0 and "CANARY_OK" in r2.stdout \
                        and "doesn't match the machine type" \
                        not in r2.stderr \
                        and "supported on the host machine" \
                        not in r2.stderr:
                    return "safe"
                # the reload leg itself warned or crashed: THE hazard
                return "unsafe"
            # r1 failing is infrastructure, not a reload verdict
            return None
        except Exception:
            return None                        # fail-safe: cache off
        finally:
            import shutil

            shutil.rmtree(cache, ignore_errors=True)

    verdict = _persistent_probe(
        _ROUNDTRIP_MEMO, (tag, ver),
        f"{os.path.normpath(scoped_dir)}.{ver}.roundtrip",
        ("safe", "unsafe"), probe)
    return verdict == "safe"


def gated_cpu_cache(base: str):
    """THE one entry point for pointing an XLA:CPU run at a persistent
    compilation cache: host-scoped path when this box round-trips its
    own entries, ``None`` (= leave the cache off) when it does not.
    Every place that sets ``jax_compilation_cache_dir`` or
    ``JAX_COMPILATION_CACHE_DIR`` for a forced-CPU run must go through
    here — a direct ``host_scoped_cpu_cache`` call reopens the
    same-host reload abort this module exists to close."""
    scoped = host_scoped_cpu_cache(base)
    return scoped if cpu_cache_roundtrip_safe(scoped) else None


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process and
    return the directory in effect (None = off: a forced-CPU run on a
    box that cannot reload its own XLA:CPU entries).

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is whatever it
    names and this function sets no other; unset, it is
    ``<checkout>/.jax_cache`` (under ``JAX_PLATFORMS=cpu``, its
    ``gated_cpu_cache`` sub-directory).  Every compile is cached,
    however short: the serving engine's warm-up and pre-warm grids are
    dozens of sub-second programs the 1 s default threshold would drop.
    """
    import jax

    cache = os.environ.get(CACHE_ENV)
    if not cache:
        cache = os.path.join(_CHECKOUT, ".jax_cache")
        if os.environ.get("JAX_PLATFORMS", "") == "cpu":
            cache = gated_cpu_cache(cache)
        if cache is not None:
            jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache
