"""AOT compiled-step cache: switch dispatch must never wait on XLA.

§5.4's "swaps plans with minimal overhead" has two halves.  Parameter state
is free by construction — (k, b) never touch the parameters — but on a JIT
engine the *compiled executable* is not: tracing + XLA compilation of a
pipeline step easily dwarfs an iteration.  This cache makes the compile
cost invisible to the switch path:

* entries are keyed by the **lowered plan identity**
  (:meth:`CompiledStepCache.plan_key` — the schedule coordinates plus a
  digest of the tabular grid, so a ``+Wopt``-refined lowering and its base
  plan are distinct entries while re-lowering the same plan is a hit);
* :meth:`precompile` AOT-compiles (``jit(...).lower(...).compile()``)
  on a background worker thread, so the tuner's top-N candidates are
  compiled while training continues under the current plan;
* :meth:`get` — the switch path — returns a ready executable (warm hit),
  waits for an in-flight background compile (precompile hit), or compiles
  synchronously as the last resort (cold miss, counted against the hit
  rate the benchmark trajectory tracks).

The cache is engine-agnostic: it is constructed with a ``program_factory``
returning ``(jittable_fn, example_args)`` for a given
:class:`~repro.core.schedule.TabularPlan`, which is how the reference and
``shard_map`` executors (and tests) plug in.

Across processes, :func:`enable_persistent_cache` points JAX's persistent
compilation cache at one fixed directory, so a second run of an entry point
loads its executables instead of compiling them again.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
import dataclasses
import hashlib
import os
from pathlib import Path
import threading
import time
from typing import Any, Callable, Iterable

import jax

from repro.core.schedule import TabularPlan
from repro.obs.metrics import MetricsRegistry

__all__ = [
    "CompiledEntry",
    "CacheStats",
    "CompiledStepCache",
    "PERSISTENT_CACHE_DIR",
    "enable_persistent_cache",
]

#: the persistent cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed directory of the checkout (git-ignored).  Never a temporary or
#: per-process name — a directory that moves between runs never hits.
PERSISTENT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory: ``$JAX_COMPILATION_CACHE_DIR`` where that is set
    (JAX reads the variable itself; no other directory is set), else
    :data:`PERSISTENT_CACHE_DIR`.  Entry points call this before their
    first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(PERSISTENT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class CompiledEntry:
    key: tuple
    compiled: Any  # the AOT-compiled executable (callable)
    compile_seconds: float
    source: str  # "precompile" | "demand"


@dataclasses.dataclass
class CacheStats:
    """Back-compat aggregate view; the live counters are registry series
    (``cache_*_total`` on :attr:`CompiledStepCache.metrics`) and
    :attr:`CompiledStepCache.stats` materializes this dataclass from them."""

    gets: int = 0
    warm_hits: int = 0  # entry ready at get() time
    inflight_hits: int = 0  # background compile already running; get() joined it
    cold_misses: int = 0  # nothing in flight: compiled synchronously
    precompile_requests: int = 0
    precompiled: int = 0  # background compiles completed

    @property
    def hit_rate(self) -> float:
        """Fraction of dispatches served by the precompile pipeline (ready
        or in flight) rather than a synchronous cold compile."""
        return (self.warm_hits + self.inflight_hits) / self.gets if self.gets else 0.0


class CompiledStepCache:
    def __init__(
        self,
        program_factory: Callable[[TabularPlan], tuple[Callable, tuple]],
        max_workers: int = 1,
        metrics: MetricsRegistry | None = None,
        labels: dict[str, str] | None = None,
    ) -> None:
        self._factory = program_factory
        self._lock = threading.Lock()
        self._entries: dict[tuple, CompiledEntry] = {}
        self._inflight: dict[tuple, Future] = {}
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="plan-precompile"
        )
        self.metrics = metrics or MetricsRegistry()
        # ``labels`` scope this cache's series on a SHARED registry (e.g. an
        # in-process fleet labels per host track) so per-cache stats stay
        # per-cache while every number lives in one place
        self._labels = dict(labels or {})
        self._gets = self.metrics.counter("cache_gets_total")
        self._warm = self.metrics.counter("cache_warm_hits_total")
        self._joined = self.metrics.counter("cache_inflight_hits_total")
        self._cold = self.metrics.counter("cache_cold_misses_total")
        self._requests = self.metrics.counter("cache_precompile_requests_total")
        self._done = self.metrics.counter("cache_precompiled_total")
        self._compile_s = self.metrics.histogram("cache_compile_seconds")

    @property
    def stats(self) -> CacheStats:
        """Aggregate view assembled from the registry counters; the dataclass
        shape (and ``dataclasses.asdict``-ability) is unchanged from when it
        was mutable state."""
        return CacheStats(
            gets=int(self._gets.value(**self._labels)),
            warm_hits=int(self._warm.value(**self._labels)),
            inflight_hits=int(self._joined.value(**self._labels)),
            cold_misses=int(self._cold.value(**self._labels)),
            precompile_requests=int(self._requests.value(**self._labels)),
            precompiled=int(self._done.value(**self._labels)),
        )

    # -- identity -------------------------------------------------------------

    @staticmethod
    def plan_key(table: TabularPlan) -> tuple:
        """Lowered-plan identity: the plan's :class:`ScheduleSpec` (the
        same frozen coordinate currency candidates and tuning records
        carry) + shape + grid digest.

        Two plans with the same coordinates but different lowerings (e.g. a
        ``+Wopt`` refinement) must not share an executable — the engine's
        unrolled tick program IS the grid."""
        p = table.plan
        digest = hashlib.sha1(table.grid.tobytes()).hexdigest()[:16]
        return (
            p.name,
            p.spec,
            p.num_stages,
            p.num_microbatches,
            digest,
        )

    # -- compilation ----------------------------------------------------------

    def _compile(self, table: TabularPlan, source: str) -> CompiledEntry:
        key = self.plan_key(table)
        t0 = time.perf_counter()
        fn, example_args = self._factory(table)
        compiled = fn.lower(*example_args).compile()
        entry = CompiledEntry(
            key=key,
            compiled=compiled,
            compile_seconds=time.perf_counter() - t0,
            source=source,
        )
        with self._lock:
            self._entries[key] = entry
            self._inflight.pop(key, None)
        self._compile_s.observe(entry.compile_seconds, source=source, **self._labels)
        if source == "precompile":
            self._done.inc(**self._labels)
        return entry

    def precompile(self, tables: Iterable[TabularPlan]) -> int:
        """Submit background AOT compiles for every not-yet-known table;
        returns how many were actually submitted."""
        submitted = 0
        for table in tables:
            key = self.plan_key(table)
            with self._lock:
                if key in self._entries or key in self._inflight:
                    continue
                fut = self._pool.submit(self._compile, table, "precompile")
                self._inflight[key] = fut
                submitted += 1
            self._requests.inc(**self._labels)
        return submitted

    def get(self, table: TabularPlan) -> CompiledEntry:
        """The switch path: ready entry, else join the in-flight background
        compile, else compile synchronously (cold)."""
        key = self.plan_key(table)
        self._gets.inc(**self._labels)
        with self._lock:
            entry = self._entries.get(key)
            fut = None if entry is not None else self._inflight.get(key)
        if entry is not None:
            self._warm.inc(**self._labels)
            return entry
        if fut is not None:
            self._joined.inc(**self._labels)
            return fut.result()
        entry = self._compile(table, "demand")
        self._cold.inc(**self._labels)
        return entry

    def contains(self, table: TabularPlan) -> bool:
        """True iff a dispatch right now would be a warm hit."""
        with self._lock:
            return self.plan_key(table) in self._entries

    def background(self, fn: Callable[[], Any]) -> Future:
        """Run an arbitrary warmup job on the precompile worker (used by the
        runtime to AOT-compile re-stacking programs alongside step
        programs); tracked by :meth:`wait_idle` via its own future."""
        fut = self._pool.submit(fn)
        key = ("__background__", id(fut))
        with self._lock:
            self._inflight[key] = fut

        def _done(_f: Future) -> None:
            with self._lock:
                self._inflight.pop(key, None)

        fut.add_done_callback(_done)
        return fut

    def wait_idle(self) -> None:
        """Block until every background compile has finished (benchmarks use
        this to measure genuinely warm switch latency)."""
        while True:
            with self._lock:
                futs = list(self._inflight.values())
            if not futs:
                return
            for f in futs:
                f.result()

    def shutdown(self) -> None:
        self._pool.shutdown(wait=True)
