"""One place for JAX's persistent compilation cache, and for what this
process compiles.

A BERT-large step takes minutes to compile, so what a second phase or
a second process costs is decided by whether it finds the first one's
cache.  The cache's path is part of its key: every process of a run
must come to the same directory, and that directory must not move
between runs.

``enable()`` also subscribes to JAX's monitoring events and turns every
phase of every compilation request into a cold span
``hvd/compile/<phase>`` with ``program=<fun_name>`` (``hvd.spans()``):
``trace`` (the jaxpr; a function traced inside another's trace is part
of that one's span), ``lower`` (to MLIR), then either ``cache_load``
(the persistent cache served it) or ``backend_compile`` (it did not).
``hvd_compile_requests_total{outcome}`` counts the requests: ``hit``,
``miss`` (compiled, and stored for the next run) or ``uncached``
(compiled and not stored: the cache is off, or JAX found the program
too quick or too small to keep).  This is what says which program
compiled in the middle of a run, and what to watch for a step that
retraces.

``first_call(jitted, kind)`` is what the step builders hand out: the
jitted function, whose first call is the cold span
``hvd/program/first_call`` from before the call until its outputs are
ready.  The request's ``hvd/compile/*`` spans are its children; what it
holds beyond them is the executable's load onto the device and its
first run.
"""

import os
import threading
import time

from . import env as env_mod
from . import metrics
from . import timeline

ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(env=os.environ) -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when
    ``env`` sets it, else ``<checkout>/.jax_cache``."""
    return env.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for this process, start recording
    its compilations, and return the cache's directory.  Where the
    variable is set JAX reads it by itself and no directory is set in
    code."""
    where = cache_dir()
    if not env_mod.env_str_opt(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", where)
    _subscribe()
    return where


# ---------------------------------------------------------------------------
# JAX's compile events as spans
# ---------------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_REQUESTS = metrics.counter(
    "hvd_compile_requests_total",
    "Compilation requests of this process by outcome: hit (loaded from "
    "the persistent cache), miss (compiled and stored), uncached "
    "(compiled, not stored)")

_subscribed = False
_subscribe_lock = threading.Lock()
# What the cache said about the request this thread is in: JAX reports
# hit or miss, and the retrieval's seconds, before the
# backend_compile_duration that closes the request and names it.  And
# how many traces this thread is inside: tracing one step of a deep
# model traces thousands of small functions within it, and only the
# outermost is a span.
_request = threading.local()


def _on_scalar(event: str, value: float, **_):
    if event == _TRACE:    # JAX reports the start of a trace this way
        _request.tracing = getattr(_request, "tracing", 0) + 1


def _on_event(event: str, **_):
    if event == _CACHE_HIT:
        _request.outcome = "hit"
    elif event == _CACHE_MISS:
        _request.outcome = "miss"


def _on_duration(event: str, seconds: float, **_):
    if event == _CACHE_RETRIEVAL:
        end = timeline.wall(time.perf_counter())
        _request.load = (end - seconds, end)


def _on_time_span(event: str, start: float, end: float, fun_name="", **_):
    if event == _TRACE:
        _request.tracing = max(getattr(_request, "tracing", 1) - 1, 0)
        if not _request.tracing:
            timeline.record("compile/trace", start, end, program=fun_name)
    elif event == _LOWER:
        timeline.record("compile/lower", start, end, program=fun_name)
    elif event == _BACKEND_COMPILE:
        outcome = getattr(_request, "outcome", "uncached")
        load = getattr(_request, "load", None)
        _request.outcome, _request.load = "uncached", None
        _REQUESTS.inc(1, outcome=outcome)
        if outcome == "hit" and load is not None:
            timeline.record("compile/cache_load", load[0], load[1],
                            program=fun_name)
        else:
            timeline.record("compile/backend_compile", start, end,
                            program=fun_name)


def _subscribe():
    global _subscribed
    with _subscribe_lock:
        if _subscribed:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_scalar_listener(_on_scalar)
        _subscribed = True


# ---------------------------------------------------------------------------
# A program's first call
# ---------------------------------------------------------------------------

class first_call:
    """``jitted`` with its first call inside a cold span
    ``hvd/program/first_call`` (``program=`` the function's name,
    ``kind=`` "init" or "step"), which ends when that call's outputs
    are ready.  Every later call tests one flag and goes to ``jitted``;
    ``.lower``, ``.trace`` and whatever else a jitted function has are
    ``jitted``'s own."""

    def __init__(self, jitted, kind: str):
        self._jitted = jitted
        self._kind = kind
        self._called = False

    def __call__(self, *args, **kwargs):
        if self._called:
            return self._jitted(*args, **kwargs)
        self._called = True
        import jax
        with timeline.span("program/first_call", cold=True,
                           program=self._jitted.__name__, kind=self._kind):
            return jax.block_until_ready(self._jitted(*args, **kwargs))

    def __getattr__(self, name):
        # Not self._jitted: on an instance that has none yet (a copy
        # being made) that would come back here for ever.
        return getattr(object.__getattribute__(self, "_jitted"), name)
