"""Compile/retrace telemetry (aux subsystem: observability).

Every jit entry point in the stack reports here: how many times each
function compiled, with which arg-shape signature, how long the
compiles took, and — the number that actually explains a slow TPU step
— how many of those compiles were RETRACES of a function that had
already compiled. tpulint's TPL002 finds retrace *hazards* statically;
this registry is its runtime counterpart, catching the storms that
only shapes-at-runtime can produce.

Mechanics: a `tracked()` wrapper keys calls by the pytree of arg
shapes/dtypes (+ static arg values) — the same thing jax's jit cache
keys on — so a first-seen signature IS a compile. The first call with
a new signature is timed wall-clock; for jax.jit that call blocks
through trace+lower+compile (execution stays async), so the elapsed
time is compile time plus one dispatch, which is the honest cost the
caller paid.

When one function crosses `warn_after` compiles, a warning fires ONCE
through the structured log + flight recorder (runtime TPL002) naming
the churning signatures.

Exposition: `render_prometheus()` emits `pt_compile_total`,
`pt_compile_retraces_total`, `pt_compile_seconds_total` (+ per-function
labelled series); the serving server appends it to `/metrics`.

`ensure_compile_cache()` is the ONE place the persistent XLA compile
cache is placed (the serving engine, the train-step builder and
chip_smoke.py call it): where `JAX_COMPILATION_CACHE_DIR` is set, jax
reads it itself and nothing is assigned here; otherwise the cache lives
at `<checkout>/.jax_cache` — a fixed path, never one built from a temp
name, pid or time: a directory that moves between runs never hits.
Hits are counted from jax's own `/jax/compilation_cache/cache_hits`
monitoring event, not guessed from wall time.
"""
from __future__ import annotations

import functools
import os
import threading
import time

from .._env import env_int

__all__ = ["CompileRegistry", "REGISTRY", "tracked", "track_jit",
           "signature_of", "set_context", "snapshot",
           "render_prometheus", "reset", "ensure_compile_cache"]

DEFAULT_WARN_AFTER = env_int("PADDLE_TPU_RETRACE_WARN")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_listening = False      # jax's listener list only grows: register once


def signature_of(args, kwargs=None):
    """Hashable arg-shape signature: arrays (anything with
    shape+dtype, incl. Tensors via their value) become
    ('shape', 'dtype'); everything else contributes its repr — the
    static-arg half of jit's cache key. Pytrees are flattened with
    jax's registry so custom nodes (Tensor) decompose correctly."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs or {}))

    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{tuple(shape)}:{dtype}"
        r = repr(x)
        return r if len(r) <= 80 else r[:77] + "..."
    return (str(treedef),) + tuple(leaf_sig(l) for l in leaves)


class _FnStats:
    __slots__ = ("name", "calls", "compiles", "compile_seconds",
                 "signatures", "last_signature", "warned")

    def __init__(self, name):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.signatures = {}       # sig -> call count
        self.last_signature = None
        self.warned = False

    def snap(self):
        return {
            "calls": self.calls,
            "compiles": self.compiles,
            "retraces": max(self.compiles - 1, 0),
            "compile_seconds": self.compile_seconds,
            "distinct_signatures": len(self.signatures),
            "last_signature": list(self.last_signature or ()),
        }


class CompileRegistry:
    def __init__(self, warn_after=DEFAULT_WARN_AFTER, warn_hook=None):
        self._lock = threading.Lock()
        self._fns = {}
        self._context = None
        self.warn_after = warn_after
        # warn_hook(name, stats_dict) — default: structured log event +
        # flight-recorder entry (set at call time so tests can swap it)
        self.warn_hook = warn_hook
        # persistent XLA compilation cache: the directory
        # ensure_compile_cache() placed it in, and how many executables
        # jax loaded from it instead of compiling (its own monitoring
        # event) — the restart-runbook signal that a warm restart
        # skipped its recompiles (docs/reliability.md).
        self.persistent_cache_dir = None
        self.cache_hits = 0

    def note_cache_hit(self):
        with self._lock:
            self.cache_hits += 1

    def set_context(self, **tags):
        """One-shot annotation consumed by the NEXT reported call: when
        that call turns out to be a compile, the tags ride its flight
        "compile" record. The bucketed serving entry points tag the
        power-of-two bucket they chose (`bucket=...`) so a retrace
        storm names the bucket that caused it."""
        with self._lock:
            self._context = tags or None

    # -- reporting -----------------------------------------------------
    def note_call(self, name, signature, elapsed_s=None, cache_hit=False):
        """Record one call; returns True when it was a compile (the
        signature was never seen for this function). `cache_hit`: jax
        loaded an executable from the persistent cache during it."""
        with self._lock:
            st = self._fns.get(name)
            if st is None:
                st = self._fns[name] = _FnStats(name)
            st.calls += 1
            st.last_signature = signature
            context, self._context = self._context, None
            compiled = signature not in st.signatures
            st.signatures[signature] = st.signatures.get(signature, 0) + 1
            if compiled:
                st.compiles += 1
                if elapsed_s is not None:
                    st.compile_seconds += elapsed_s
                retrace = st.compiles > 1
                warn = (not st.warned and
                        st.compiles >= self.warn_after)
                if warn:
                    st.warned = True
                snap = st.snap()
        if not compiled:
            return False
        from . import flight_recorder as _fr
        _fr.record("compile", fn=name, retrace=retrace,
                   n_compiles=snap["compiles"],
                   elapsed_s=elapsed_s, cache_hit=cache_hit,
                   signature=list(signature)[:8],
                   **(context or {}))
        if warn:
            self._warn(name, snap)
        return True

    def _warn(self, name, snap):
        hook = self.warn_hook
        if hook is not None:
            hook(name, snap)
            return
        from . import logging as _log
        _log.get_logger("compile").event(
            "compile.retrace_storm", level="warning", fn=name,
            compiles=snap["compiles"],
            distinct_signatures=snap["distinct_signatures"],
            compile_seconds=snap["compile_seconds"],
            hint=("same function recompiled repeatedly — a shape or "
                  "static-arg churns per call; bucket the shape or hoist "
                  "the static (tpulint TPL002, now observed at runtime)"))

    # -- wrapping ------------------------------------------------------
    def tracked(self, name=None):
        """Decorator: report every call of the wrapped (usually jitted)
        callable to this registry under `name`."""
        def deco(fn):
            label = name or getattr(fn, "__name__", repr(fn))
            from ..profiler import record_span

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                # this wrapper's own share of the caller's time, as two
                # short `pt.track_jit` spans around the call and never
                # the call itself (in a serving turn: part `telemetry`)
                with record_span("pt.track_jit", part="telemetry"):
                    # signature BEFORE the call: donated buffers are
                    # invalid afterwards
                    try:
                        sig = signature_of(args, kwargs)
                    except Exception:   # never let telemetry break the call
                        sig = ("<unhashable>",)
                    hits0 = self.cache_hits
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                elapsed = time.perf_counter() - t0
                with record_span("pt.track_jit", part="telemetry"):
                    compiled = self.note_call(
                        label, sig, elapsed_s=elapsed,
                        cache_hit=self.cache_hits > hits0)
                    # device cost accounting: a compile captures the new
                    # executable's XLA cost/memory analysis (shape-only
                    # AOT re-resolve — donated buffers are fine), and
                    # every call adds its known FLOPs to the MFU window
                    from . import device_telemetry as _dt
                    if compiled:
                        _dt.COSTS.capture(label, sig, fn, args, kwargs)
                    _dt.COSTS.note_executed(label, sig)
                return out
            wrapper.__wrapped__ = fn
            wrapper._pt_compile_name = label
            return wrapper
        return deco

    # -- exposition ----------------------------------------------------
    def totals(self):
        with self._lock:
            return {
                "compiles": sum(s.compiles for s in self._fns.values()),
                "retraces": sum(max(s.compiles - 1, 0)
                                for s in self._fns.values()),
                "compile_seconds": sum(s.compile_seconds
                                       for s in self._fns.values()),
                "functions": len(self._fns),
                "cache_hits": self.cache_hits,
            }

    def snapshot(self):
        with self._lock:
            return {name: st.snap() for name, st in self._fns.items()}

    def render_prometheus(self):
        t = self.totals()
        out = [
            "# HELP pt_compile_total jit compilations observed "
            "(first call per arg-shape signature).",
            "# TYPE pt_compile_total counter",
            f"pt_compile_total {t['compiles']}",
            "# HELP pt_compile_retraces_total compilations beyond each "
            "function's first (retraces).",
            "# TYPE pt_compile_retraces_total counter",
            f"pt_compile_retraces_total {t['retraces']}",
            "# HELP pt_compile_seconds_total wall seconds paid "
            "compiling (first-call elapsed).",
            "# TYPE pt_compile_seconds_total counter",
            f"pt_compile_seconds_total {t['compile_seconds']:.6f}",
            "# HELP pt_compile_cache_hits_total compiles served from "
            "the persistent XLA compilation cache.",
            "# TYPE pt_compile_cache_hits_total counter",
            f"pt_compile_cache_hits_total {t['cache_hits']}",
        ]
        with self._lock:
            stats = sorted(self._fns.values(), key=lambda s: s.name)
            rows = [(s.name, s.compiles, max(s.compiles - 1, 0),
                     s.compile_seconds) for s in stats]
        out.append("# TYPE pt_compile_fn_total counter")
        for name, compiles, retraces, secs in rows:
            out.append(f'pt_compile_fn_total{{fn="{name}"}} {compiles}')
        out.append("# TYPE pt_compile_fn_retraces_total counter")
        for name, compiles, retraces, secs in rows:
            out.append(
                f'pt_compile_fn_retraces_total{{fn="{name}"}} {retraces}')
        out.append("# TYPE pt_compile_fn_seconds_total counter")
        for name, compiles, retraces, secs in rows:
            out.append(
                f'pt_compile_fn_seconds_total{{fn="{name}"}} {secs:.6f}')
        return "\n".join(out) + "\n"

    def reset(self):
        with self._lock:
            self._fns.clear()
            self.cache_hits = 0


REGISTRY = CompileRegistry()


def _on_jax_event(event, **_):
    if event == _CACHE_HIT_EVENT:
        REGISTRY.note_cache_hit()


def ensure_compile_cache():
    """Place jax's persistent compilation cache, once per process, and
    return its directory. `JAX_COMPILATION_CACHE_DIR` set: jax uses it
    and `jax_compilation_cache_dir` is assigned nowhere; unset:
    `<checkout>/.jax_cache`. Also starts counting jax's cache-hit
    events into `REGISTRY.cache_hits`."""
    global _listening
    with REGISTRY._lock:
        if REGISTRY.persistent_cache_dir is not None:
            return REGISTRY.persistent_cache_dir
        import jax
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache_dir:
            cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        REGISTRY.persistent_cache_dir = cache_dir
        if not _listening:
            jax.monitoring.register_event_listener(_on_jax_event)
            _listening = True
    return cache_dir


def tracked(name=None, registry=None):
    """Module-level decorator bound to the global registry."""
    return (registry or REGISTRY).tracked(name)


# jit entry points read better as: prefill = track_jit("serving.prefill")(prefill)
track_jit = tracked


def set_context(**tags):
    """Tag the global registry's next reported call (see
    CompileRegistry.set_context)."""
    REGISTRY.set_context(**tags)


def snapshot():
    return REGISTRY.snapshot()


def render_prometheus():
    return REGISTRY.render_prometheus()


def reset():
    REGISTRY.reset()
