"""Phase-level tracing: device scopes and host spans (Fig. 8 taxonomy).

The paper's evidence is per-phase time breakdowns (Fig. 8: sampling / SpMM /
GEMM / communication). Two halves produce them:

* **device scopes** — :func:`phase` is ``jax.named_scope``: the name lands
  in every lowered op's ``op_name`` metadata (transposes included), so a
  profiler trace of the compiled program can be bucketed by phase. It
  exists at trace time only and costs nothing at run time. ``PHASES`` is
  the one list of the program's scopes; none of them nests in another.
* **host spans** — :class:`Tracer` times the runtime's host boundaries
  (``Trainer``: ``run``, each ``chunk``'s dispatch, the end-of-run loss
  ``drain``, ``eval``, ``ckpt``, ``ckpt_wait``, the async writer's
  ``ckpt_io`` and ``compile``): named spans aggregate (count, total
  seconds, max) per path, and an enabled span also opens a
  ``jax.profiler.TraceAnnotation`` (``repro.<name>``), so the spans sit in
  a profiler trace on the device timeline's clock. The tracer has

  - **near-zero overhead when disabled** — ``span()`` returns ONE shared
    no-op context manager (no allocation, no clock read, no annotation),
    so instrumentation can stay in hot paths unconditionally;
  - **thread safety** — the span stack is thread-local (each thread nests
    independently: the async-checkpoint worker and the serving pump thread
    record concurrently with the driver), aggregation is lock-protected;
  - **nesting** — a span opened inside another records under the joined
    path (``"run/chunk"``), so the summary keeps the call structure;
  - **compile accounting** — :meth:`Tracer.compiles` records each program
    built on the calling thread (traced, lowered, compiled) as a
    ``compile`` span under the open span's path, from JAX's monitoring
    events;
  - **jax.profiler passthrough** — ``trace_dir`` forwards to
    ``jax.profiler.start_trace`` for device-level timelines.

A span measures host wall time, so spans belong at host boundaries
(per-chunk dispatch, the loss drain, eval, checkpoint), never inside a
``jit`` trace, where they would time the tracing.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

import jax

# The program's device scopes, in step order: sampling, unpacking the CSR
# shards, extraction (Fig. 8), the input projection, the layer loop's
# phases (Fig. 8), the output head, the loss and the optimizer update.
PHASES = ("sample", "unpack", "extract", "proj", "spmm", "gemm", "reshard",
          "tail", "rotate", "head", "loss", "optim")
# JAX's monitoring events of building one program, in the order they end
_BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")


class _NullSpan:
    """The shared disabled-mode span: no state, no clock, no allocation."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "_name", "_t0", "_ann", "path", "seconds")

    def __init__(self, tracer: "Tracer", name: str, step: Optional[int]):
        self._tracer = tracer
        self._name = name
        self.path = name
        self.seconds = 0.0
        label = "repro." + name
        self._ann = (jax.profiler.TraceAnnotation(label) if step is None
                     else jax.profiler.StepTraceAnnotation(label,
                                                           step_num=step))

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        stack = self._tracer._stack()
        stack.append(self._name)
        self.path = "/".join(stack)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._tracer._stack().pop()
        self._tracer._record(self.path, self.seconds)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Aggregating span recorder. ``span(name)`` is the only hot-path API."""

    def __init__(self, enabled: bool = True,
                 trace_dir: Optional[str] = None):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self._lock = threading.Lock()
        self._local = threading.local()
        # path -> [count, total_s, max_s]
        self._stats: Dict[str, list] = {}
        self._profiling = False

    # -- spans ---------------------------------------------------------------

    def span(self, name: str, step: Optional[int] = None):
        """Context manager timing one host span, annotated in a profiler
        trace as ``repro.<name>`` (a step annotation carrying ``step`` as
        its ``step_num`` when given); the no-op singleton when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, step)

    def compiles(self):
        """Context manager recording every program built on the calling
        thread while it is open (traced, lowered and compiled, or read
        back from the persistent cache) as one ``compile`` span under
        that thread's open span path, from JAX's monitoring events; the
        no-op singleton when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return self._watch_compiles(threading.get_ident())

    @contextlib.contextmanager
    def _watch_compiles(self, thread: int):
        built = []      # disjoint (start, end) of the program being built

        def on_span(event, start, end, **kw):
            if event not in _BUILD_EVENTS or threading.get_ident() != thread:
                return
            while built and built[-1][0] >= start:  # nested in this one
                built.pop()
            built.append((start, end))
            if event == _BUILD_EVENTS[-1]:          # the program is built
                self._record("/".join(self._stack() + ["compile"]),
                             sum(e - s for s, e in built))
                built.clear()

        jax.monitoring.register_event_time_span_listener(on_span)
        try:
            yield
        finally:
            jax.monitoring.unregister_event_time_span_listener(on_span)

    def record(self, name: str, seconds: float) -> None:
        """Record an externally-measured duration under ``name``."""
        if self.enabled:
            self._record(name, seconds)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _record(self, path: str, seconds: float) -> None:
        with self._lock:
            ent = self._stats.get(path)
            if ent is None:
                self._stats[path] = [1, seconds, seconds]
            else:
                ent[0] += 1
                ent[1] += seconds
                ent[2] = max(ent[2], seconds)

    # -- reporting -----------------------------------------------------------

    def summary(self) -> Dict[str, Dict[str, float]]:
        """``{path: {count, total_s, mean_ms, max_ms}}`` for every span path
        recorded so far."""
        with self._lock:
            return {
                path: {
                    "count": c,
                    "total_s": tot,
                    "mean_ms": tot / c * 1e3,
                    "max_ms": mx * 1e3,
                }
                for path, (c, tot, mx) in sorted(self._stats.items())
            }

    def total(self, name: str) -> float:
        """Total seconds across every path whose LEAF phase is ``name``
        (``total("eval")`` includes ``"chunk/eval"``)."""
        with self._lock:
            return sum(tot for path, (_, tot, _) in self._stats.items()
                       if path.rsplit("/", 1)[-1] == name)

    def totals(self) -> Dict[str, float]:
        """Leaf-phase totals (the Fig. 8 breakdown input)."""
        out: Dict[str, float] = {}
        with self._lock:
            for path, (_, tot, _) in self._stats.items():
                leaf = path.rsplit("/", 1)[-1]
                out[leaf] = out.get(leaf, 0.0) + tot
        return out

    def reset(self) -> None:
        with self._lock:
            self._stats = {}

    # -- jax.profiler passthrough -------------------------------------------

    def start_profile(self) -> bool:
        """Start a ``jax.profiler`` trace into ``trace_dir`` (no-op without
        one). Returns whether a trace was started."""
        if self.trace_dir is None or self._profiling:
            return False
        jax.profiler.start_trace(self.trace_dir)
        self._profiling = True
        return True

    def stop_profile(self) -> None:
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False


# ---------------------------------------------------------------------------
# The process-global tracer, for callers without a tracer of their own
# (benchmark scripts). Disabled by default.
# ---------------------------------------------------------------------------

_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    global _GLOBAL
    _GLOBAL = tracer
    return tracer


def phase(name: str):
    """Scope one of the program's ``PHASES`` in library code: a
    ``jax.named_scope``, which labels the ops traced inside it (and their
    transposes) in the compiled program's metadata and the profiler's
    timeline. No host span: under ``jit`` it would time the tracing."""
    assert name in PHASES, f"{name!r} is not one of the program's PHASES"
    return jax.named_scope(name)
