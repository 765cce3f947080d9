"""Compile-latency control: persistent compilation cache + compile meter.

The bench says XLA compilation -- not evolution steps -- is the serving
tail-latency killer: a cold `PlacementService` blocks tens of seconds on
backend compiles before its first generation, and every geometric-ladder
`grow()` or lazily-created scheduler pool repeats the bill.  This module is
the runtime half of the fix (the serving half is `serve.prewarm`):

  * **persistent cache** -- `enable()` turns on jax's persistent
    compilation cache with thresholds zeroed, so
    EVERY program the service compiles (step, init, warm-init, fill, at
    every slot-ladder size) is serialized to disk.  A restarted process --
    or a CI runner restoring the directory -- deserializes instead of
    recompiling: jax keys entries on the lowered computation plus its own
    jax/XLA-version and device-topology salt, so the per-pool-signature
    keying the scheduler needs falls out for free (a different `PoolKey`
    lowers to a different program; a jax upgrade or device-count change
    can never serve a stale binary).
  * **compile meter** -- a process-wide counter/timer fed by
    `jax.monitoring` events: total backend-compile requests, real compile
    seconds, and persistent-cache hits/misses.  `recompiles` is the number
    of *real* XLA compiles (requests not answered by the cache), the
    quantity the CI compile budget pins at zero for a warm start.
    `measure()` scopes the count to the calling thread, which is how
    `PlacementService` separates *blocking* compiles (in the stepping
    loop's thread) from background prewarm compiles.

Where the cache lives is placed from outside: `$JAX_COMPILATION_CACHE_DIR`
when it is set (jax reads that variable itself, and nothing here sets
another directory), else one fixed path inside the checkout,
`<checkout>/.jax_cache` (gitignored).  A fixed path matters because the
directory is part of what a later run must find again.  Entry points
(`launch/serve.py`, `chip_smoke.py`, the benchmarks) call `enable()`;
importing the library never turns the cache on.  This module imports jax
only inside the functions that need it, so a parent process that only
launches probes can resolve `cache_dir()` without taking a device.

Nothing here is load-bearing for results: the cache and the meter change
when compilation happens, never what the compiled programs produce, and
with neither enabled the service is bitwise the pre-PR code path.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import warnings
from pathlib import Path
from typing import Any, Dict, Optional

# jax.monitoring event keys.  A jax upgrade that renames them
# degrades the meter to "nothing observed" -- callers treat 0-compiles-seen
# with `events_seen == 0` as "meter unavailable", never as "no compiles".
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class _Scope:
    """One `measure()` window: compiles observed on the opening thread."""

    __slots__ = ("compiles", "secs")

    def __init__(self) -> None:
        self.compiles = 0
        self.secs = 0.0


class CompileMeter:
    """Process-wide compile counter/timer (jax.monitoring listeners).

    Counters:
      * `compiles`      -- backend-compile *requests* (cache-served ones
        included: jax fires the compile event either way),
      * `compile_secs`  -- wall seconds inside those requests (a cache hit
        costs milliseconds of deserialization, a miss costs the real
        compile),
      * `cache_hits` / `cache_misses` -- persistent-cache outcomes (only
        fire while the cache is enabled),
      * `recompiles`    -- real XLA compiles: `compiles - cache_hits`,
        uniform whether or not the persistent cache is on.

    `measure()` additionally scopes compile counts to the calling thread
    for the duration of a `with` block, so a service can attribute
    compiles to the exact blocking entry point (submit/step/grow) that
    triggered them while a background prewarm thread compiles freely.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self.compiles = 0
        self.compile_secs = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.events_seen = 0
        self._scopes: Dict[int, list] = {}     # thread id -> open scope stack

    # ------------------------------------------------------------ install

    def install(self) -> "CompileMeter":
        """Register the monitoring listeners (idempotent; listeners are
        process-permanent, so there is exactly one global meter)."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        try:
            from jax import monitoring
            monitoring.register_event_listener(self._on_event)
            monitoring.register_event_duration_secs_listener(self._on_dur)
        except Exception as e:                       # pragma: no cover
            warnings.warn(f"compile meter unavailable ({e}); compile "
                          "counts will read 0", stacklevel=2)
        return self

    def _on_event(self, name: str, **kw: Any) -> None:
        if name == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1
                self.events_seen += 1
        elif name == _CACHE_MISS:
            with self._lock:
                self.cache_misses += 1
                self.events_seen += 1

    def _on_dur(self, name: str, secs: float, **kw: Any) -> None:
        if name != _BACKEND_COMPILE:
            return
        tid = threading.get_ident()
        with self._lock:
            self.compiles += 1
            self.compile_secs += secs
            self.events_seen += 1
            for scope in self._scopes.get(tid, ()):
                scope.compiles += 1
                scope.secs += secs

    # ------------------------------------------------------------ reading

    @property
    def recompiles(self) -> int:
        """Real XLA compiles (requests the persistent cache did not
        answer).  With the cache off no hit events fire, so this equals
        `compiles`; with it on it equals `cache_misses`."""
        return self.compiles - self.cache_hits

    @contextlib.contextmanager
    def measure(self):
        """Scope compile counting to the calling thread::

            with meter.measure() as m:
                jitted_fn(args)          # may compile
            m.compiles, m.secs           # compiles on THIS thread only
        """
        scope = _Scope()
        tid = threading.get_ident()
        with self._lock:
            self._scopes.setdefault(tid, []).append(scope)
        try:
            yield scope
        finally:
            with self._lock:
                self._scopes[tid].remove(scope)
                if not self._scopes[tid]:
                    del self._scopes[tid]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "recompiles": self.recompiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compile_secs": round(self.compile_secs, 3),
                "events_seen": self.events_seen,
                "persistent_cache_dir": enabled_dir(),
            }

    def telemetry_samples(self):
        """The meter as `(name, kind, help, value)` rows for the metrics
        registry's collect walk (`runtime.telemetry` registers a collector
        over this), so compile counters land in the same Prometheus scrape
        as job counters instead of living in a side dict."""
        with self._lock:
            return [
                ("repro_compiles_total", "counter",
                 "Backend compile requests (cache-served included)",
                 float(self.compiles)),
                ("repro_recompiles_total", "counter",
                 "Real XLA compiles (requests not answered by the "
                 "persistent cache)", float(self.recompiles)),
                ("repro_compile_seconds_total", "counter",
                 "Wall seconds inside backend compile requests",
                 round(self.compile_secs, 6)),
                ("repro_compile_cache_hits_total", "counter",
                 "Persistent compilation cache hits",
                 float(self.cache_hits)),
                ("repro_compile_cache_misses_total", "counter",
                 "Persistent compilation cache misses",
                 float(self.cache_misses)),
            ]


_METER = CompileMeter()


def meter() -> CompileMeter:
    """The process-global compile meter (listeners installed lazily by the
    first `install()`; `PlacementService` installs on construction)."""
    return _METER


# --------------------------------------------------------------- enabling

_ENABLED_DIR: Optional[str] = None

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The persistent-cache directory entry points use:
    `$JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.jax_cache`."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Enable jax's persistent compilation cache at `cache_dir()`.

    Thresholds are zeroed (`min_entry_size`/`min_compile_time`) so every
    service program persists -- the pool-shaped programs are individually
    small but collectively the whole cold-start bill.  Safe to call more
    than once.  Returns the directory.
    """
    import jax

    global _ENABLED_DIR
    directory = cache_dir()
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _reset_jax_cache_latch()
    _ENABLED_DIR = directory
    meter().install()
    return directory


def _reset_jax_cache_latch() -> None:
    """jax latches its "is the cache used?" decision at the FIRST compile
    of the process; a process that compiled anything before `enable()`
    (imports with eager ops, a test suite, a service enabling mid-flight)
    would silently never persist.  `reset_cache()` un-latches it so the
    new directory takes effect."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def disable() -> None:
    """Turn the persistent cache back off (tests; the listener-based meter
    stays installed -- listeners are process-permanent)."""
    import jax

    global _ENABLED_DIR
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache_latch()
    _ENABLED_DIR = None


def enabled_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when disabled."""
    return _ENABLED_DIR


# ------------------------------------------------------------------- salt

def cache_salt() -> str:
    """Human-readable jax-version/backend/device-count salt.

    jax already folds all of this into its persistent-cache keys; this
    string exists for the layers *around* the cache -- CI `actions/cache`
    keys and prewarm bookkeeping -- so they partition storage the same way
    the entries inside it are partitioned."""
    import jax
    return (f"jax{jax.__version__}-{jax.default_backend()}"
            f"-d{jax.device_count()}")


def pool_token(pool_key: Any) -> str:
    """Stable short token for one pool signature under the current salt
    (prewarm bookkeeping / stats labels; not a jax cache key)."""
    text = repr((pool_key, cache_salt()))
    return hashlib.sha1(text.encode()).hexdigest()[:16]
