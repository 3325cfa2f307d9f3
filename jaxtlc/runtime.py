"""Process-entry rules shared by every front door: which platform a run
may use, how many devices a mesh may claim, and where compiled programs
persist.

`aot_build` is the one place an engine's program is built ahead of time
(the supervisor's adapters, `check_with_checkpoints`, the serve pool),
under the `build` spans of obs.spans; `CompileMeter` counts what XLA did
meanwhile.  Given a key it keeps what it built (`EngineCache`, one a
process): a second check of the same spec and geometry goes straight to
its loop.

Each entry point (`api.run_check`, `jaxtlc.serve` start-up, the
`jaxtlc.dist` worker, `chip_smoke.py`) calls
`enable_compile_cache()` once and resolves its platform through
`require_platform()`; engines never do either themselves.  The point of
both is that a run cannot look like a chip run when it is not one: CPU
is used only when asked for, a mesh never shrinks to the devices that
happen to exist, and the compile cache sits where the caller (or the
checkout) put it - not under $HOME.
"""

from __future__ import annotations

import json
import os
import threading
from collections import OrderedDict
from typing import Optional

from .obs import scopes
from .obs.spans import span

# the in-checkout default, derived from the package location: the cache
# key includes the directory, so a path that moves never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


class PlatformError(RuntimeError):
    """The resolved JAX platform or device count is not what the run
    asked for (entry points turn this into exit code 1)."""


def enable_compile_cache() -> str:
    """Persist every XLA compile of this process; returns the directory.

    JAX_COMPILATION_CACHE_DIR set: JAX already has the directory and it
    is left alone.  Unset: the fixed `<checkout>/.jax_cache`.  The
    persistence thresholds are zeroed either way - the fused engine
    loops are exactly the long compiles the cache exists for, and the
    small ones cost nothing to keep.  JAX_ENABLE_COMPILATION_CACHE=false
    is JAX's own off switch."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cpu_requested(workers: str = "") -> bool:
    """Whether CPU was asked for: `-workers cpu`, or a JAX platform
    list (JAX_PLATFORMS / jax.config) that leads with cpu."""
    import jax

    platforms = jax.config.jax_platforms or ""
    return workers == "cpu" or platforms.split(",")[0].strip() == "cpu"


def require_platform(workers: str = "") -> str:
    """Resolve the platform this process runs on and return its name.

    CPU is a platform only when asked for (`cpu_requested`); any other
    request must resolve to an accelerator - JAX drops to CPU without a
    word when it finds no chip, and a run that then reports rates is
    the failure this guards against."""
    import jax

    if workers == "cpu" and not cpu_requested():
        # effective only before the backend initializes, which is when
        # the CLI calls this; afterwards the platform is already fixed
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu_requested(workers):
        raise PlatformError(
            "JAX found no accelerator and resolved to cpu; to run on "
            "CPU ask for it: pass -workers cpu or set JAX_PLATFORMS=cpu"
        )
    return platform


def fp_mesh(n_devices: int = 0):
    """The single-axis "fp" mesh over the first `n_devices` devices
    (0 = all of them).  Asking for more devices than exist is an error,
    never a smaller mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices > len(devices):
        raise PlatformError(
            f"{n_devices} devices requested but JAX reports "
            f"{len(devices)} ({devices[0].platform})"
        )
    return Mesh(np.array(devices[:n_devices] if n_devices else devices),
                ("fp",))


class CompileMeter:
    """Process-wide XLA backend-compile counter (jax.monitoring).

    Counts `/jax/core/compile/backend_compile_duration` events - fired
    once per real XLA compile (AOT .compile() included, persistent-
    cache hits included: deserialization still passes through the
    event), never by a warm executable call.  Monotonic; assert on
    deltas.
    `cache_hits` counts the persistent-cache hits among them
    (`/jax/compilation_cache/cache_hits`): `count - cache_hits` is the
    number of programs the backend actually compiled, which is what a
    second process over a warm cache must see at zero.  `retrieval_s`
    sums `/jax/compilation_cache/cache_retrieval_time_sec`: what those
    hits spent fetching and deserializing their executables."""

    _instance: Optional["CompileMeter"] = None

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.wall_s = 0.0
        self.cache_hits = 0
        self.retrieval_s = 0.0
        self._lock = threading.Lock()

        def on_duration(name, duration, **kw):
            if name.endswith("backend_compile_duration"):
                with self._lock:
                    self.count += 1
                    self.wall_s += float(duration)
            elif name.endswith("cache_retrieval_time_sec"):
                with self._lock:
                    self.retrieval_s += float(duration)

        def on_event(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        # registration failing raises: with no listener "warm submit =
        # 0 compiles" would be vacuous, so there is no degraded meter
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        self.available = True

    @classmethod
    def instance(cls) -> "CompileMeter":
        if cls._instance is None:
            cls._instance = CompileMeter()
        return cls._instance

    def read(self) -> tuple:
        with self._lock:
            return (self.count, self.cache_hits, self.wall_s,
                    self.retrieval_s)


# Kept engines hold device memory (a template carry is the fingerprint
# table and the queue: ~0.2 GB for a 2^24-slot table on one chip, 1.1 GB
# for the four-chip flagship, whole on device 0 where the mesh engine's
# init_fn leaves it; and ~50 MB a device of loaded executable), so the
# cap is small and fixed
ENGINE_CACHE_CAP = 4


class by_identity:
    """A key part that names an object by identity (a SpecBackend: a
    tuple of closures with no value to hash).  It holds the object, so
    the id cannot be reused while a kept entry carries the key."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, by_identity) and other.obj is self.obj


def engine_key(kind: str, program, meta: dict, *more) -> tuple:
    """The key a per-call builder states for `aot_build`: the route's
    kind, the program's identity (the frozen ModelConfig the hand kernel
    is built from, else the SpecBackend object itself, by identity), the
    checkpoint meta its geometry resolves to (every parameter that
    shapes the program or the carry: a resume compares the same dict),
    and whatever else the build reads (`check_deadlock`, the segment
    length, the Mesh).  Never derived from the `make` closure."""
    from .config import ModelConfig

    if not isinstance(program, ModelConfig):
        program = by_identity(program)
    return (kind, program, json.dumps(meta, sort_keys=True)) + more


class EngineCache:
    """Bounded, thread-safe LRU of built engines: key -> (template
    carry, compiled executable).  A build runs outside the lock, one
    builder a key: `claim` gives the kept pair, or None to the one
    caller who is to build it and `settle` it (a second caller of a key
    being built waits for the first).  A build that raises settles
    nothing and keeps nothing."""

    def __init__(self, cap: int):
        self.cap = cap
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._building: dict = {}  # key -> Event set when its build ends
        self.hits = self.misses = self.evictions = 0

    def claim(self, key) -> Optional[tuple]:
        while True:
            with self._lock:
                pair = self._entries.get(key)
                if pair is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    return pair
                ended = self._building.get(key)
                if ended is None:
                    self._building[key] = threading.Event()
                    self.misses += 1
                    return None
            ended.wait()

    def settle(self, key, pair: Optional[tuple]) -> None:
        """The claimed build ended: with `pair`, or (None) by raising."""
        with self._lock:
            if pair is not None:
                self._entries[key] = pair
                while len(self._entries) > self.cap:
                    self._entries.popitem(last=False)
                    self.evictions += 1
            ended = self._building.pop(key)
        ended.set()

    def drop(self, keep=None) -> int:
        """Forget every entry but the one whose executable is `keep`;
        the number dropped (they count as evictions)."""
        with self._lock:
            gone = [k for k, (_, compiled) in self._entries.items()
                    if compiled is not keep]
            for k in gone:
                del self._entries[k]
            self.evictions += len(gone)
        return len(gone)

    def clear(self) -> None:
        """Forget every entry and zero the counters (a build in flight
        still settles)."""
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0

    def stats(self) -> dict:
        with self._lock:
            return dict(hits=self.hits, misses=self.misses,
                        evictions=self.evictions,
                        size=len(self._entries), cap=self.cap)


_ENGINES = EngineCache(ENGINE_CACHE_CAP)


def engine_cache_stats() -> dict:
    """hits, misses, evictions, size, cap of the kept engines (`/pool`
    republishes it beside the struct memo's)."""
    return _ENGINES.stats()


def drop_kept_engines(keep=None) -> int:
    """Free the device memory the kept engines hold, all but the entry
    whose executable is `keep` (the supervisor's first answer to a
    device out-of-memory: other checks' engines go before its ladder
    takes a rung).  Returns how many went."""
    return _ENGINES.drop(keep)


def clear_engine_cache() -> None:
    """Forget every kept engine and zero the counters (tests)."""
    _ENGINES.clear()


def _debug_build() -> bool:
    """Either variable a build reads from outside its arguments is set
    (engine/reduce.py's lying remap table, analysis/donation.py's
    poisoning wrapper): such a build is not kept."""
    from .analysis.donation import debug_donation_enabled

    return (os.environ.get("JAXTLC_DEBUG_SYM_LIE", "") == "1"
            or debug_donation_enabled())


def aot_build(make, key=None):
    """Build one engine program ahead of time: `make()` gives (init_fn,
    jitted program of one carry); returns (template carry, compiled
    executable).  The `build` span and its five children say where a
    build's time goes - the engine factory's Python, `init_fn()`, the
    trace to a jaxpr, the lowering to MLIR, and `.compile()`, which on a
    warm process is the persistent cache's fetch plus the load of the
    executable onto the device (`requests`, `cache_hits`, `backend_s`,
    `retrieval_s` on `build.compile` are CompileMeter's deltas).

    With a `key` (`engine_key`) the pair is kept process-wide and a
    second build under the same key returns it without calling `make`:
    the caller must build with donate=False and never consume the
    template (the four per-call builders feed it back as the first carry
    and read it only).  `build` says which it was (`engine_cache`: hit,
    miss, or off: no key, or a debug variable set); a hit still closes
    `build.trace`, `build.lower` and `build.compile` (`requests` 0), each
    the microseconds it took, so what reads them reads what this check
    paid.  A miss builds right here, no frame deeper than an unkeyed
    build: a trace's host seconds move with the depth it starts from
    (PERF.md section 6, PR 24).  Every executable built here, keyed or
    not, is registered with obs.scopes (a weak reference, no parse): its
    instruction -> `jaxtlc.*` scope table is derived the first time a
    profile asks for it."""
    if key is not None and _debug_build():
        key = None
    meter = CompileMeter.instance()
    with span("build") as whole:
        kept = _ENGINES.claim(key) if key is not None else None
        whole.attrs["engine_cache"] = (
            "off" if key is None else "miss" if kept is None else "hit")
        if kept is not None:
            for name in ("build.trace", "build.lower"):
                with span(name):
                    pass
            with span("build.compile", requests=0):
                pass
            return kept
        built = None
        try:
            with span("build.engine"):
                init_fn, program = make()
            with span("build.init"):
                template = init_fn()
            with span("build.trace"):
                traced = program.trace(template)
            with span("build.lower"):
                lowered = traced.lower()
            before = meter.read()
            with span("build.compile") as s:
                compiled = lowered.compile()
                n, hits, backend_s, retrieval_s = (
                    b - a for a, b in zip(before, meter.read()))
                s.attrs.update(requests=n, cache_hits=hits,
                               backend_s=round(backend_s, 6),
                               retrieval_s=round(retrieval_s, 6))
            scopes.register(compiled)  # its scope table, if ever asked
            built = (template, compiled)
        finally:
            if key is not None:
                _ENGINES.settle(key, built)
    return built
