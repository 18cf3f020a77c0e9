"""Sandbox interpreter shim, loaded into every user process via PYTHONPATH.

TPU-native growth of the reference's sitecustomize (executor/sitecustomize.py:
1-31). Keeps the reference's headless-display patches and adds the numpy→XLA
reroute. Everything is installed through one lazy ``__import__`` patch so
interpreter startup stays free: nothing heavy imports until user code itself
imports the module in question.

Patches:
- ``numpy``           → XLA reroute entry points (runtime/xla_reroute.py)
- ``matplotlib.pyplot.show``  → ``savefig("plot.png")``   (headless pods)
- ``PIL`` ``ImageShow.show``  → ``img.save("image.png")``
- ``moviepy`` ``write_videofile``: logger silenced (tqdm noise in stderr)
- ``torch``           → if torch_xla is importable, make "xla" the default
                        device so torch code lands on the TPU too
- ``jax``             → if BCI_PROFILE_DIR is set, capture a jax.profiler
                        trace of the whole run into that directory
"""

import builtins
import sys

_patched = set()
_original_import = builtins.__import__
# True while the image's own (shadowed) sitecustomize executes: imports it
# performs are platform infrastructure (plugin registration often pulls in
# numpy), not the user "importing numpy" — patching then would (a) install the
# reroute before the request env is even visible and (b) wrap numpy for
# processes that never use it. Defer: the module stays in sys.modules and gets
# patched at the first post-site import statement instead.
_deferring = False
# Set for real once the shadowed sitecustomize (if any) is located, below;
# must exist before the __import__ patch is installed.
_chain_pending = False
_chain_finder = None

import threading as _threading

_chain_lock = _threading.Lock()


def _patch_numpy(numpy):
    try:
        try:
            from bee_code_interpreter_tpu.runtime import xla_reroute
        except ImportError:
            # Sandbox interpreters get only this shim dir on PYTHONPATH; the
            # shim ships inside the package tree (…/bee_code_interpreter_tpu/
            # runtime/shim/sitecustomize.py), so the directory *containing* the
            # package is four dirname()s up from this file.
            import os

            root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
            if root not in sys.path:
                sys.path.append(root)
            from bee_code_interpreter_tpu.runtime import xla_reroute

        xla_reroute.install(numpy)
    except Exception:
        pass


def _patch_pyplot(pyplot):
    def show(*_args, **_kwargs):
        pyplot.savefig("plot.png")

    pyplot.show = show


def _patch_pil(image_show):
    def show(img, *_args, **_kwargs):
        img.save("image.png")
        return True

    image_show.show = show


def _patch_moviepy_editor(editor):
    try:
        original = editor.VideoClip.write_videofile

        def write_videofile(self, *args, **kwargs):
            kwargs.setdefault("logger", None)
            return original(self, *args, **kwargs)

        editor.VideoClip.write_videofile = write_videofile
    except Exception:
        pass


def _patch_torch(torch):
    try:
        import torch_xla.core.xla_model as xm  # noqa: F401

        torch.set_default_device("xla")
    except Exception:
        pass  # CPU torch stays CPU torch


def _patch_jax_profiler(jax):
    """BCI_PROFILE_DIR=<dir> captures a jax.profiler trace of the whole run.

    The trace starts when user code first imports jax and stops at interpreter
    exit; written under the workspace it rides the executor's changed-file
    snapshot back to the client (SURVEY.md §5 "add jax.profiler trace capture
    endpoints in the sandbox") — no separate download channel needed.
    """
    import atexit
    import os

    trace_dir = os.environ.get("BCI_PROFILE_DIR")
    if not trace_dir:
        return
    jax.profiler.start_trace(trace_dir)

    def _stop():
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass

    atexit.register(_stop)


_PATCHES = {
    "numpy": _patch_numpy,
    "matplotlib.pyplot": _patch_pyplot,
    "PIL.ImageShow": _patch_pil,
    "moviepy.editor": _patch_moviepy_editor,
    "torch": _patch_torch,
    "jax": _patch_jax_profiler,
}


# Accelerator-adjacent top-level imports that must see the image's own site
# hooks (PJRT plugin registration) before they initialize. Anything else
# (numpy, pandas, requests, …) runs fine without them — which is what makes
# the deferred chain safe.
_CHAIN_TRIGGERS = {
    "jax", "jaxlib", "flax", "optax", "orbax", "torch", "torch_xla",
    "tensorflow",
}


class _ChainTriggerFinder:
    """Meta-path tripwire: fire the deferred chain on the first attempt to
    import an accelerator library, whatever the import mechanism — a meta
    importer sees importlib.import_module and entry-point loaders too,
    which a builtins.__import__ patch alone would miss. Never provides a
    module itself (find_spec always defers to the real finders)."""

    def find_spec(self, fullname, path=None, target=None):
        if (
            _chain_pending
            and not _deferring
            and fullname.partition(".")[0] in _CHAIN_TRIGGERS
        ):
            _exec_chained_sitecustomize()
        return None


def _import(name, globals=None, locals=None, fromlist=(), level=0):
    module = _original_import(name, globals, locals, fromlist, level)
    if _deferring:
        return module
    for target, patch in _PATCHES.items():
        if target in _patched or target not in sys.modules:
            continue
        candidate = sys.modules[target]
        # Don't touch a module that is still executing its own package init
        # (sys.modules holds partially-initialized modules during import) —
        # patches applied then would be overwritten by the init itself.
        spec = getattr(candidate, "__spec__", None)
        if spec is not None and getattr(spec, "_initializing", False):
            continue
        _patched.add(target)
        try:
            patch(candidate)
        except Exception:
            pass
    return module


builtins.__import__ = _import


def _find_next_sitecustomize():
    """Path of the next sitecustomize.py further down sys.path, if any.

    Python imports only the *first* sitecustomize it finds; since this shim
    is prepended to PYTHONPATH it would otherwise shadow the sandbox image's
    own site hooks (e.g. the PJRT/TPU plugin registration some images
    perform there). Cooperate instead of replacing."""
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    for entry in sys.path:
        try:
            candidate = os.path.join(entry or ".", "sitecustomize.py")
            if os.path.abspath(os.path.dirname(candidate)) == here:
                continue
            if not os.path.isfile(candidate):
                continue
        except OSError:
            continue
        # abspath NOW: relative sys.path entries must not break the chain
        # after user code chdirs before its first accelerator import
        return os.path.abspath(candidate)
    return None


_chain_path = _find_next_sitecustomize()
_chain_pending = _chain_path is not None
_chain_finder = None
if _chain_pending:
    _chain_finder = _ChainTriggerFinder()
    sys.meta_path.insert(0, _chain_finder)


def _exec_chained_sitecustomize():
    global _deferring, _chain_pending
    with _chain_lock:
        # re-check under the lock: two threads importing different
        # accelerator libs concurrently must not run the chain twice
        # (duplicate PJRT registration / atexit hooks)
        if not _chain_pending:
            return
        _chain_pending = False
        import importlib.util

        try:
            _deferring = True
            spec = importlib.util.spec_from_file_location(
                "_chained_sitecustomize", _chain_path
            )
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
        except Exception:
            pass
        finally:
            _deferring = False
    if _chain_finder is not None:
        try:
            sys.meta_path.remove(_chain_finder)
        except ValueError:
            pass


# The image's site hooks exist to prime accelerator plugins — work worth
# ~1 s of jax import in this image's case. Paying that on EVERY interpreter
# start taxes the pool-refill rate (and with it warm latency) for the many
# payloads that never touch an accelerator, so by default the chain is
# DEFERRED to the first accelerator-adjacent import (see _CHAIN_TRIGGERS in
# _import). BCI_EAGER_CHAIN=1 restores start-time chaining for images whose
# hooks do more than accelerator setup.
import os as _os

if _chain_pending and _os.environ.get("BCI_EAGER_CHAIN") == "1":
    _exec_chained_sitecustomize()
