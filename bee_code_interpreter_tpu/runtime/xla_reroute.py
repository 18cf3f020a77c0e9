"""Transparent numpy → XLA rerouting for LLM-submitted code.

The TPU-native growth of the reference's 31-line sitecustomize display shim
(executor/sitecustomize.py:6-31; SURVEY.md §2: "grows ... into the
numpy/torch→XLA rerouting layer"). User code keeps writing plain numpy; dense
compute transparently lands on the attached TPU:

- **Entry points**: the handful of numpy APIs where the FLOPs are — matmul,
  dot, einsum, tensordot, and the big elementwise/reduction producers — are
  wrapped. When an input crosses a size threshold (default 1M elements) and
  dtypes are XLA-friendly, the op executes via jax.numpy on the default device
  and returns a ``TpuArray``.
- **Stickiness**: ``TpuArray`` implements ``__array_function__`` and
  ``__array_ufunc__``, so *subsequent* numpy calls on it (np.sum, np.exp,
  np.mean, arithmetic, comparisons, slicing) dispatch straight to jax.numpy and
  stay on device — chains like ``np.sum(np.square(x))`` run fused on TPU
  without bouncing through host memory.
- **Graceful fallback** (SURVEY.md §7 hard part (b)): anything that needs a
  real ndarray — pandas, scipy, file I/O, ``np.asarray``, unknown numpy
  functions — hits ``__array__`` and materializes to host numpy transparently.
  Small arrays never leave numpy in the first place.

Nothing here imports jax at interpreter startup: wrappers are installed by an
import hook (see shim/sitecustomize.py) and jax loads lazily on the first
large-array hit. Set ``BCI_XLA_REROUTE=0`` to disable, or
``BCI_XLA_REROUTE_MIN_ELEMS`` to tune the threshold. Both are re-read at
**call time**, not only at install time: a warm (pre-started) sandbox installs
the proxies before the request env is applied, and user code that sets the
flag after numpy is already imported must still get the documented opt-out.

The first device placement is guarded by a backend-init watchdog
(``BCI_XLA_INIT_TIMEOUT_S``, default 30s): if jax's backend cannot come up —
the chip is held by another process (a chip belongs to one process at a
time), no accelerator is attached, or init blocks — the reroute permanently
falls back to host numpy instead of failing or hanging the user's plain-numpy
script. That IS the module's "graceful fallback" promise applied to the
backend itself; it is for USER numpy code only, and ``backend_status()`` says
which backend the reroute landed on (or why it did not), so nothing that
measures the device mistakes a host run for one.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable

_DEFAULT_MIN_ELEMS = 1 << 20

_jnp = None
_np = None


def _enabled() -> bool:
    """Per-call opt-out check — see module docstring for why not install-time."""
    return os.environ.get("BCI_XLA_REROUTE", "1") != "0"


def _min_elems() -> int:
    raw = os.environ.get("BCI_XLA_REROUTE_MIN_ELEMS")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return _DEFAULT_MIN_ELEMS


def _jax_numpy():
    global _jnp
    if _jnp is None:
        # jax's import chain (ml_dtypes) registers custom dtypes against the
        # *real* numpy ufuncs; importing it with our proxies installed breaks
        # that C-level registration. Restore originals around the import.
        with _pristine_numpy():
            import jax.numpy as jnp

        _jnp = jnp
    return _jnp


def _numpy():
    global _np
    if _np is None:
        import numpy as np

        _np = np
    return _np


_REROUTE_DTYPES = frozenset(
    {"float16", "float32", "float64", "bfloat16", "int8", "int16", "int32",
     "int64", "uint8", "uint32", "bool", "complex64"}
)


def _eligible(value: Any) -> bool:
    np = _numpy()
    return (
        isinstance(value, np.ndarray)
        and value.size >= _min_elems()
        and str(value.dtype) in _REROUTE_DTYPES
    )


# None = not yet probed, True = backend usable, False = init failed/timed out
# (reroute then stays on host numpy for the life of the process).
_backend_state: bool | None = None
_backend_platform: str | None = None
_backend_error: str | None = None
_backend_lock = threading.Lock()


def backend_status() -> dict:
    """Where the reroute landed: ``probed`` (has any eligible call asked
    yet), ``ok``, the jax ``platform`` it places arrays on, and ``error``
    when the probe failed or timed out (host numpy from then on)."""
    return {
        "probed": _backend_state is not None,
        "ok": _backend_state,
        "platform": _backend_platform,
        "error": _backend_error,
    }


def _backend_ok() -> bool:
    """One-time watchdogged jax backend probe.

    jax backend init is the one step the reroute cannot survive failing
    mid-expression: an init that raises (chip held by another process, no
    device) or blocks would turn "transparent acceleration" into a crash or
    a silent multi-minute hang of a plain-numpy script. Probe it once on a
    daemon thread with a deadline; on timeout or error, disable rerouting
    permanently, keep the reason for ``backend_status()``, and let every
    entry point fall through to host numpy.
    """
    global _backend_state, _backend_platform, _backend_error
    if _backend_state is not None:
        return _backend_state
    with _backend_lock:
        if _backend_state is not None:
            return _backend_state
        # Default 30s: comfortably above a healthy cold TPU init (~10-20s)
        # but well under the default 60s execution timeout, so a blocked
        # backend still leaves the user's script time to finish on host.
        try:
            timeout_s = float(os.environ.get("BCI_XLA_INIT_TIMEOUT_S", "30"))
        except ValueError:
            timeout_s = 30.0
        outcome: list[str | Exception] = []

        def probe() -> None:
            try:
                # jax's import chain registers dtypes against the *real*
                # numpy entry points (see _jax_numpy) — this probe is usually
                # the process's first jax import, so the same pristine guard
                # applies here.
                with _pristine_numpy():
                    import jax

                    outcome.append(jax.devices()[0].platform)
            except Exception as e:
                outcome.append(e)

        thread = threading.Thread(
            target=probe, name="bci-xla-init-probe", daemon=True
        )
        thread.start()
        thread.join(timeout_s)
        if outcome and isinstance(outcome[0], str):
            _backend_platform = outcome[0]
        else:
            _backend_error = (
                repr(outcome[0]) if outcome
                else f"backend init still blocked after {timeout_s:g}s"
            )
        _backend_state = _backend_platform is not None
    return _backend_state


def _to_device(value: Any):
    import jax

    return jax.device_put(value)


class TpuArray:
    """A device-resident array that keeps numpy code on the TPU.

    Wraps a jax.Array. numpy protocol hooks dispatch numpy API calls to
    jax.numpy by name; materialization happens only when host data is truly
    needed (``__array__``).
    """

    __slots__ = ("_jax",)
    # Higher than numpy's default so our protocol hooks win.
    __array_priority__ = 200

    def __init__(self, jax_array) -> None:
        self._jax = jax_array

    # -- introspection ----------------------------------------------------
    @property
    def shape(self):
        return self._jax.shape

    @property
    def dtype(self):
        return self._jax.dtype

    @property
    def ndim(self):
        return self._jax.ndim

    @property
    def size(self):
        return self._jax.size

    @property
    def T(self):
        return TpuArray(self._jax.T)

    @property
    def jax_array(self):
        """The underlying jax.Array, for code that wants to go native."""
        return self._jax

    @property
    def device(self):
        # Array-API device probe (numpy 2.x ndarray.device == "cpu"). scipy's
        # array-api-compat reads this on hypothesis-test results and feeds it
        # back into numpy-namespace asarray(..., device=...); reporting the
        # host view keeps that interop path working (SURVEY.md §7 hard part b:
        # reroute must not break pandas/scipy).
        return "cpu"

    def to_device(self, device, /, *, stream=None):
        if device == "cpu":
            return self
        raise ValueError(f"unsupported device: {device!r}")

    def __repr__(self):
        # Human output renders like numpy (pandas/print paths call str/repr on
        # cell objects); materializing here is fine — repr is for humans.
        return repr(self._jax.item()) if self._jax.ndim == 0 else repr(self.__array__())

    def __str__(self):
        return str(self._jax.item()) if self._jax.ndim == 0 else str(self.__array__())

    def __format__(self, spec):
        value = self._jax.item() if self._jax.ndim == 0 else self.__array__()
        return format(value, spec)

    def __len__(self):
        return self._jax.shape[0] if self._jax.ndim else 0

    # -- materialization (the graceful-fallback path) ---------------------
    def __array__(self, dtype=None, copy=None):
        host = _numpy().asarray(self._jax)
        return host.astype(dtype) if dtype is not None else host

    def __float__(self):
        return float(self._jax)

    def __int__(self):
        return int(self._jax)

    def __bool__(self):
        return bool(self._jax)

    def __iter__(self):
        return iter(_numpy().asarray(self._jax))

    def astype(self, dtype):
        return TpuArray(self._jax.astype(dtype))

    def item(self):
        return self._jax.item()

    # numpy ndarray conveniences used pervasively by user code
    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return TpuArray(self._jax.reshape(shape))

    def sum(self, *args, **kwargs):
        return _wrap(self._jax.sum(*args, **kwargs))

    def mean(self, *args, **kwargs):
        return _wrap(self._jax.mean(*args, **kwargs))

    def max(self, *args, **kwargs):
        return _wrap(self._jax.max(*args, **kwargs))

    def min(self, *args, **kwargs):
        return _wrap(self._jax.min(*args, **kwargs))

    def transpose(self, *axes):
        return TpuArray(self._jax.transpose(*axes))

    def copy(self):
        return TpuArray(self._jax)

    def __getitem__(self, idx):
        return _wrap(self._jax[_unwrap(idx)])

    # -- numpy protocol hooks: ops on TpuArray stay on device -------------
    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        fn = (
            getattr(_jax_numpy(), ufunc.__name__, None)
            if method == "__call__"
            else None
        )
        if fn is not None and kwargs.get("out") is None:
            return _wrap(fn(*map(_unwrap, inputs), **kwargs))
        # Graceful CPU fallback (SURVEY.md §7 hard part b): ufuncs with no
        # jax.numpy equivalent (e.g. scipy.special.stdtr), reduce/accumulate
        # forms, and out= targets run on host views. Materializing here (not
        # returning NotImplemented) matters: numpy defers to TpuArray's higher
        # __array_priority__, so bailing would poison the whole expression.
        out = kwargs.get("out")
        if out is not None and any(isinstance(o, TpuArray) for o in out):
            return NotImplemented  # jax arrays are immutable; no in-place target
        if method == "at":
            # np.add.at(x, idx, v) mutates x in place; a host view of a device
            # array would swallow (or, where the view aliases the buffer,
            # corrupt) the update. Refuse loudly instead.
            return NotImplemented
        np = _numpy()
        host_inputs = [
            np.asarray(x) if isinstance(x, TpuArray) else x for x in inputs
        ]
        return getattr(ufunc, method)(*host_inputs, **kwargs)

    def __array_function__(self, func, types, args, kwargs):
        jnp = _jax_numpy()
        # resolve e.g. numpy.linalg.norm -> jax.numpy.linalg.norm
        module = func.__module__ or "numpy"
        target = jnp
        for part in module.split(".")[1:]:
            target = getattr(target, part, None)
            if target is None:
                return NotImplemented
        fn = getattr(target, func.__name__, None)
        if fn is None:
            return NotImplemented
        try:
            return _wrap(fn(*_unwrap_tree(args), **_unwrap_tree(kwargs)))
        except (TypeError, NotImplementedError):
            return NotImplemented


def _unwrap(value):
    return value._jax if isinstance(value, TpuArray) else value


def _unwrap_tree(value):
    if isinstance(value, TpuArray):
        return value._jax
    if isinstance(value, (list, tuple)):
        return type(value)(_unwrap_tree(v) for v in value)
    if isinstance(value, dict):
        return {k: _unwrap_tree(v) for k, v in value.items()}
    return value


def _wrap(value):
    # jax.Array results stay wrapped; everything else passes through
    import jax

    if isinstance(value, jax.Array):
        return TpuArray(value)
    if isinstance(value, (list, tuple)):
        return type(value)(_wrap(v) for v in value)
    return value


# -- arithmetic dunders (generated) ---------------------------------------

def _binop(name: str, jnp_name: str, reflected: bool = False):
    def op(self, other):
        jnp = _jax_numpy()
        fn = getattr(jnp, jnp_name)
        a, b = (_unwrap(other), self._jax) if reflected else (self._jax, _unwrap(other))
        try:
            return _wrap(fn(a, b))
        except TypeError:
            return NotImplemented

    op.__name__ = name
    return op


for _name, _jnp_name in [
    ("add", "add"), ("sub", "subtract"), ("mul", "multiply"),
    ("truediv", "true_divide"), ("floordiv", "floor_divide"), ("mod", "mod"),
    ("pow", "power"), ("matmul", "matmul"),
]:
    setattr(TpuArray, f"__{_name}__", _binop(f"__{_name}__", _jnp_name))
    setattr(TpuArray, f"__r{_name}__", _binop(f"__r{_name}__", _jnp_name, reflected=True))

for _name, _jnp_name in [
    ("lt", "less"), ("le", "less_equal"), ("gt", "greater"),
    ("ge", "greater_equal"), ("eq", "equal"), ("ne", "not_equal"),
]:
    setattr(TpuArray, f"__{_name}__", _binop(f"__{_name}__", _jnp_name))

TpuArray.__neg__ = lambda self: _wrap(_jax_numpy().negative(self._jax))
TpuArray.__abs__ = lambda self: _wrap(_jax_numpy().abs(self._jax))


# -- numpy entry-point patching -------------------------------------------

# numpy-namespace callables wrapped as reroute entry points.
#
# CONSTRAINT: never proxy a ufunc object (np.add, np.square, np.matmul, ...).
# ml_dtypes — imported by jax — registers bfloat16 loops directly on those C
# objects at import time; replacing them in the numpy namespace breaks any
# later `import jax` with "ufunc add takes N arguments". Instead:
#
# - non-ufunc compute/reduction functions are proxied (safe: plain callables)
# - array *creation* is the on-ramp: a big host array gets device-placed and
#   wrapped, after which every ufunc chain (np.square, np.exp, +, @, ...)
#   dispatches through TpuArray.__array_ufunc__ and stays on device without
#   the numpy namespace ever being touched.
ENTRY_POINTS = (
    "dot", "einsum", "tensordot", "inner", "vdot",
    "sum", "mean", "std", "var", "prod",
)

# Creation functions wrapped so large results start life on the TPU. Random
# values are generated by host numpy first (identical RNG semantics, one h2d
# transfer), shape/fill creations go straight to the device.
CREATION_FUNCS = ("zeros", "ones", "full", "arange", "linspace")
RANDOM_FUNCS = ("rand", "randn", "random", "uniform", "standard_normal")


class _EntryProxy:
    """Callable proxy over a numpy function/ufunc.

    Calls with a large-array operand reroute to jax.numpy; everything else —
    including attribute access like ``np.add.reduce``, ``np.square.types``,
    ``np.matmul.at`` that third-party libraries rely on — forwards to the
    original object untouched.
    """

    __slots__ = ("__wrapped__", "_name")

    def __init__(self, original, name: str) -> None:
        object.__setattr__(self, "__wrapped__", original)
        object.__setattr__(self, "_name", name)

    def __call__(self, *args, **kwargs):
        # _backend_ok() last: small/ineligible calls must never pay (or hang
        # on) backend init, and a disabled reroute must not probe at all.
        if (
            _enabled()
            and any(_eligible(a) for a in args)
            and not kwargs.get("out")
            and _backend_ok()
        ):
            fn = getattr(_jax_numpy(), self._name, None)
            if fn is not None:
                try:
                    moved = [
                        _to_device(a) if _eligible(a) else _unwrap(a) for a in args
                    ]
                    return _wrap(fn(*moved, **_unwrap_tree(kwargs)))
                except Exception:
                    pass  # fall back to host numpy below
        return self.__wrapped__(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.__wrapped__, name)

    def __repr__(self):
        return repr(self.__wrapped__)

    # class attributes (docstring, class name) shadow __getattr__; forward the
    # introspection attrs explicitly — numpy.ma parses np.<fn>.__doc__ at init
    @property
    def __doc__(self):  # type: ignore[override]
        return self.__wrapped__.__doc__

    @property
    def __name__(self):
        return getattr(self.__wrapped__, "__name__", self._name)


import contextlib


@contextlib.contextmanager
def _pristine_numpy():
    """Temporarily restore the original numpy entry points."""
    np = _np
    if np is None or not getattr(np, "__bci_xla_rerouted__", False):
        yield
        return
    saved = {}
    for name in ENTRY_POINTS:
        current = getattr(np, name, None)
        if isinstance(current, _EntryProxy):
            saved[name] = current
            setattr(np, name, current.__wrapped__)
    try:
        yield
    finally:
        for name, proxy in saved.items():
            setattr(np, name, proxy)


class _CreationProxy:
    """Wraps an array-creation function: big results start life on the TPU."""

    __slots__ = ("__wrapped__", "_host_first")

    def __init__(self, original, host_first: bool) -> None:
        object.__setattr__(self, "__wrapped__", original)
        # host_first: run the original (RNG semantics!) then device-place;
        # otherwise the result is value-deterministic and the wrap is free.
        object.__setattr__(self, "_host_first", host_first)

    def __call__(self, *args, **kwargs):
        host = self.__wrapped__(*args, **kwargs)
        if _enabled() and _eligible(host) and _backend_ok():
            try:
                return TpuArray(_to_device(host))
            except Exception:
                pass
        return host

    def __getattr__(self, name):
        return getattr(self.__wrapped__, name)

    def __repr__(self):
        return repr(self.__wrapped__)

    @property
    def __doc__(self):  # type: ignore[override]
        return self.__wrapped__.__doc__

    @property
    def __name__(self):
        return getattr(self.__wrapped__, "__name__", "creation")



def install(numpy_module=None) -> bool:
    """Patch the numpy module's entry points. Idempotent. Returns success.

    Note the proxies re-check ``BCI_XLA_REROUTE`` on every call, so installing
    while the flag is off would be harmless — but honoring it here too keeps
    the explicitly-opted-out interpreter entirely proxy-free.
    """
    if not _enabled():
        return False
    np = numpy_module
    if np is None:
        import numpy as np
    global _np
    _np = np
    if getattr(np, "__bci_xla_rerouted__", False):
        return True
    for name in ENTRY_POINTS:
        original = getattr(np, name, None)
        if original is None or isinstance(original, _EntryProxy):
            continue
        if isinstance(original, np.ufunc):  # see ENTRY_POINTS constraint
            continue
        setattr(np, name, _EntryProxy(original, name))
    for name in CREATION_FUNCS:
        original = getattr(np, name, None)
        if original is not None and not isinstance(original, (_CreationProxy, np.ufunc)):
            setattr(np, name, _CreationProxy(original, host_first=False))
    random_module = getattr(np, "random", None)
    if random_module is not None:
        for name in RANDOM_FUNCS:
            original = getattr(random_module, name, None)
            if original is not None and not isinstance(original, _CreationProxy):
                setattr(random_module, name, _CreationProxy(original, host_first=True))
    np.__bci_xla_rerouted__ = True
    return True


def uninstall(numpy_module=None) -> None:
    """Restore every proxied numpy entry point to the original callable.

    The complement ``install()`` never had: a warm sandbox whose request env
    sets ``BCI_XLA_REROUTE=0`` can now fully de-proxy numpy (the bootstrap
    calls this after applying the request env) instead of relying solely on
    the proxies' per-call flag check.
    """
    np = numpy_module
    if np is None:
        np = _np
    if np is None:
        import sys

        np = sys.modules.get("numpy")
    if np is None or not getattr(np, "__bci_xla_rerouted__", False):
        return
    for name in ENTRY_POINTS + CREATION_FUNCS:
        current = getattr(np, name, None)
        if isinstance(current, (_EntryProxy, _CreationProxy)):
            setattr(np, name, current.__wrapped__)
    random_module = getattr(np, "random", None)
    if random_module is not None:
        for name in RANDOM_FUNCS:
            current = getattr(random_module, name, None)
            if isinstance(current, _CreationProxy):
                setattr(random_module, name, current.__wrapped__)
    np.__bci_xla_rerouted__ = False
