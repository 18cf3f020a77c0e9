"""Compile-visible wrappers over jitted callables.

XLA compilation is the serving engine's biggest hidden latency source: a
decode step that normally takes ~15 ms stalls for seconds when a new
(shape, dtype) signature forces a retrace, and nothing in the process
says so. :class:`TrackedJit` wraps an already-``jax.jit``-ed callable and
reports every compilation to a duck-typed monitor (an
``observability.DeviceMonitor`` in the composed service, anything with an
``on_compile`` hook elsewhere) — function name, the abstract input
signature that triggered it, compile wall time, and whether it was the
function's first compile or a retrace.

Detection is cheap by design: jax's jit wrapper exposes ``_cache_size()``
(the number of compiled executables it holds), so the hot path pays two
integer probes and one clock read per call — the human-readable signature
is only computed on the rare call that actually compiled.

This module is stdlib-only (the arrays are duck-typed via
``shape``/``dtype``/``nbytes``) so it imports anywhere ``utils.metrics``
does; ``models/`` uses it without importing ``observability/``.
"""

from __future__ import annotations

import time
from typing import Callable

# Containers with more leaves than this are summarized (leaf count + total
# bytes) instead of spelled out — a params pytree has hundreds of leaves
# and the culprit of a retrace is virtually always a positional array
# argument, not the weights.
_MAX_SPELLED_LEAVES = 4


def _iter_leaves(x):
    if isinstance(x, dict):
        for v in x.values():
            yield from _iter_leaves(v)
    elif isinstance(x, (list, tuple)):
        for v in x:
            yield from _iter_leaves(v)
    else:
        yield x


def _leaf_signature(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        dims = ",".join(str(int(d)) for d in shape)
        return f"{getattr(dtype, 'name', dtype)}[{dims}]"
    if x is None or isinstance(x, (bool, int, float, str)):
        # static argument: its VALUE is part of the compiled signature
        return repr(x)
    return type(x).__name__


def _signature(x) -> str:
    if isinstance(x, (dict, list, tuple)):
        leaves = list(_iter_leaves(x))
        if len(leaves) > _MAX_SPELLED_LEAVES:
            nbytes = sum(int(getattr(leaf, "nbytes", 0) or 0) for leaf in leaves)
            return f"{type(x).__name__}[{len(leaves)} leaves, {nbytes}B]"
        inner = ", ".join(_leaf_signature(leaf) for leaf in leaves)
        return f"{type(x).__name__}({inner})"
    return _leaf_signature(x)


def abstract_signature(args: tuple, kwargs: dict | None = None) -> str:
    """The abstract input signature of a call: per-arg ``dtype[shape]`` for
    arrays, ``repr`` for statics, condensed summaries for large pytrees —
    enough to name the shape/dtype that caused a retrace without hashing
    gigabytes of weights."""
    parts = [_signature(a) for a in args]
    if kwargs:
        parts += [f"{k}={_signature(v)}" for k, v in sorted(kwargs.items())]
    return f"({', '.join(parts)})"


class TrackedJit:
    """Wrap a jitted callable so a monitor sees its compilations.

    ``get_monitor`` is a zero-arg callable returning the current monitor
    (or None); resolving it per call keeps the wrapper attach/detach-safe
    and makes the unmonitored path a single callable invocation plus one
    None check. Attribute access (``.lower``, ``._cache_size``) passes
    through to the wrapped jit, so AOT-lowering call sites keep working.
    """

    __slots__ = ("fn", "name", "_get_monitor")

    def __init__(self, fn, name: str, get_monitor: Callable) -> None:
        self.fn = fn
        self.name = name
        self._get_monitor = get_monitor

    def __getattr__(self, item):
        return getattr(self.fn, item)

    def __call__(self, *args, **kwargs):
        monitor = self._get_monitor()
        if monitor is None:
            return self.fn(*args, **kwargs)
        before = self.fn._cache_size()
        t0 = time.monotonic()
        out = self.fn(*args, **kwargs)
        duration_ms = (time.monotonic() - t0) * 1000.0
        if self.fn._cache_size() <= before:
            return out
        trigger = "first_call" if before == 0 else "retrace"
        signature = abstract_signature(args, kwargs)
        # duration includes the (comparatively negligible) dispatch of the
        # freshly compiled executable — it IS the stall the caller felt
        monitor.on_compile(
            self.name,
            signature=signature,
            duration_ms=duration_ms,
            trigger=trigger,
        )
        return out
