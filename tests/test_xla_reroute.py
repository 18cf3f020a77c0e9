"""numpy→XLA reroute: creation on-ramp, device stickiness, graceful fallback.

Design constraint under test: the numpy namespace's *ufunc objects are never
replaced* (ml_dtypes/jax compatibility); big arrays enter the device world at
creation or via non-ufunc reductions, then ufunc chains ride
TpuArray.__array_ufunc__."""

import numpy as np
import pytest

from bee_code_interpreter_tpu.runtime import xla_reroute
from bee_code_interpreter_tpu.runtime.xla_reroute import TpuArray


@pytest.fixture(autouse=True)
def small_threshold(monkeypatch):
    # keep tests fast: reroute anything >= 1024 elements (the threshold is
    # re-read from the env per call — the warm-path opt-out contract)
    monkeypatch.setenv("BCI_XLA_REROUTE_MIN_ELEMS", "1024")
    monkeypatch.delenv("BCI_XLA_REROUTE", raising=False)
    xla_reroute.install(np)
    yield


def big(n=64):
    return np.random.rand(n, n)  # 4096 elems >= threshold -> TpuArray


def test_ufuncs_never_proxied():
    # the ml_dtypes constraint: ufunc objects in the numpy namespace stay pristine
    for name in ("add", "multiply", "square", "sqrt", "exp", "matmul"):
        assert isinstance(getattr(np, name), np.ufunc), name


def test_small_arrays_stay_numpy():
    a = np.random.rand(4, 4)
    assert isinstance(a, np.ndarray)
    assert isinstance(np.matmul(a, a), np.ndarray)
    assert isinstance(np.sum(a), np.floating)


def test_creation_onramp_random():
    a = big()
    assert isinstance(a, TpuArray)


def test_creation_onramp_zeros_ones():
    assert isinstance(np.zeros((64, 64)), TpuArray)
    assert isinstance(np.ones(2048), TpuArray)
    assert isinstance(np.arange(5), np.ndarray)  # small stays host


def test_matmul_on_device():
    a = big()
    out = np.matmul(a, a)
    assert isinstance(out, TpuArray)
    host = np.asarray(a)
    np.testing.assert_allclose(np.asarray(out), host @ host, rtol=1e-4)


def test_chained_ufuncs_stay_on_device():
    x = big()
    squared = np.square(x)  # real ufunc -> __array_ufunc__ -> jnp
    assert isinstance(squared, TpuArray)
    total = np.sum(squared)  # proxied reduction
    assert isinstance(total, TpuArray)
    host = np.asarray(x)
    assert float(total) == pytest.approx(float((host * host).sum()), rel=1e-4)


def test_benchmark_numpy_payload():
    # the reference benchmark payload (examples/benchmark-numpy.py:19-29):
    # rand -> square -> sum, end-to-end on device
    x = np.random.rand(4096)
    assert isinstance(x, TpuArray)
    result = np.sum(np.square(x))
    assert isinstance(result, TpuArray)
    assert float(result) / 4096 == pytest.approx(1 / 3, abs=0.05)


def test_reduction_proxy_onramps_plain_ndarray():
    host = np.asarray(big())  # plain ndarray above threshold
    total = np.sum(host)
    assert isinstance(total, TpuArray)


def test_einsum_and_dot_proxies():
    a, b = big(), big()
    out = np.einsum("ij,jk->ik", a, b)
    assert isinstance(out, TpuArray)
    out2 = np.dot(np.asarray(a), np.asarray(b))
    assert isinstance(out2, TpuArray)


def test_arithmetic_dunders():
    a, b = big(), big()
    c = (a + b) * 2 - b / 3
    assert isinstance(c, TpuArray)
    d = a @ b
    assert isinstance(d, TpuArray)
    assert d.shape == (64, 64)


def test_mixed_tpu_and_numpy_operands():
    a = big()
    host = np.full((64, 64), 1.0)
    host = np.asarray(host)
    out = a + host
    assert isinstance(out, TpuArray)
    out2 = host + a  # reflected: numpy defers via __array_ufunc__/__array_priority__
    assert isinstance(out2, TpuArray)


def test_graceful_fallback_to_host():
    a = big()
    host = np.asarray(a)
    assert isinstance(host, np.ndarray)
    assert host.shape == (64, 64)
    assert float(host[0, 0]) == pytest.approx(float(a[0, 0].item()), rel=1e-5)


def test_reductions_methods_and_indexing():
    a = big()
    assert a.sum().item() == pytest.approx(float(np.asarray(a).sum()), rel=1e-4)
    assert a[:2, :3].shape == (2, 3)
    assert a.T.shape == (64, 64)
    assert a.reshape(-1).shape == (64 * 64,)
    assert len(a) == 64


def test_array_function_dispatch():
    a = big()
    out = np.percentile(a, 50)
    assert 0 <= float(out) <= 1
    stacked = np.stack([a, a])
    assert stacked.shape == (2, 64, 64)


def test_jax_importable_after_install():
    # the exact failure mode that motivated the no-ufunc-proxy design
    import importlib

    import jax

    importlib.reload(jax.numpy) if False else None
    assert jax.numpy.add(1, 2) == 3


def test_install_idempotent():
    assert xla_reroute.install(np)
    assert xla_reroute.install(np)
    assert isinstance(np.sum, xla_reroute._EntryProxy)
    assert not isinstance(np.sum.__wrapped__, xla_reroute._EntryProxy)


def test_disable_via_env(monkeypatch):
    monkeypatch.setenv("BCI_XLA_REROUTE", "0")
    import types

    fake = types.ModuleType("fake_numpy")
    fake.sum = np.sum
    assert not xla_reroute.install(fake)


def test_array_api_device_probe():
    # scipy's array-api-compat reads .device on results and feeds it back into
    # asarray(..., device=...); numpy 2.x ndarrays report "cpu".
    a = big()
    assert a.device == "cpu"
    assert a.to_device("cpu") is a
    with pytest.raises(ValueError):
        a.to_device("tpu:0")


def test_unknown_ufunc_falls_back_to_host():
    # ufuncs with no jax.numpy equivalent (scipy.special et al.) must run on
    # host views rather than returning NotImplemented — numpy defers to
    # TpuArray's higher __array_priority__, so bailing poisons the expression.
    scipy_special = pytest.importorskip("scipy.special")
    a = big()
    out = scipy_special.erf(a)
    assert isinstance(out, np.ndarray)
    assert out.shape == (64, 64)


def test_ufunc_reduce_falls_back_to_host():
    # np.add.reduce(tpu_array) dispatches __array_ufunc__ with method="reduce";
    # no jnp lookup happens for non-__call__ methods, so this exercises the
    # host-fallback branch directly on a device array.
    a = big()
    total = np.add.reduce(a.reshape(-1))
    assert isinstance(total, np.floating)
    assert float(total) == pytest.approx(float(a.sum()), rel=1e-4)


def test_ufunc_at_refuses_device_target():
    # In-place scatter on a device array must fail loudly, not write to (or
    # through) a host view of the buffer.
    a = big()
    with pytest.raises(TypeError):
        np.add.at(a, [0], 1.0)


def test_scalar_renders_like_numpy():
    # 0-d results print as plain scalars (pandas cells call str/format/repr).
    s = big().mean()
    assert "TpuArray" not in str(s)
    assert "TpuArray" not in repr(s)
    assert float(f"{s:.6f}") == pytest.approx(s.item(), abs=1e-5)


# --- round-2 hardened contract: call-time opt-out, watchdog, uninstall ------
# Round-1 failure shape (BENCH_r01.json): a warm sandbox installed the proxies
# before the request env existed, so BCI_XLA_REROUTE=0 was silently ignored
# and the first big array hung on a blocking backend init. These pin the fix.


def test_calltime_optout_entry_and_creation(monkeypatch):
    # proxies are installed, then the env flips: every subsequent call must
    # stay on host numpy (install-time-only checking is the round-1 bug)
    monkeypatch.setenv("BCI_XLA_REROUTE", "0")
    host = np.asarray(np.random.rand(64, 64))
    assert isinstance(np.matmul(host, host), np.ndarray)
    assert isinstance(np.sum(host), np.floating)
    assert isinstance(np.zeros((64, 64)), np.ndarray)
    assert isinstance(np.random.rand(64, 64), np.ndarray)


def test_min_elems_reread_from_env(monkeypatch):
    monkeypatch.setenv("BCI_XLA_REROUTE_MIN_ELEMS", str(1 << 60))
    assert isinstance(np.random.rand(64, 64), np.ndarray)
    monkeypatch.setenv("BCI_XLA_REROUTE_MIN_ELEMS", "16")
    assert isinstance(np.random.rand(8, 8), TpuArray)


def test_uninstall_restores_numpy():
    assert getattr(np, "__bci_xla_rerouted__", False)
    xla_reroute.uninstall(np)
    try:
        assert not np.__bci_xla_rerouted__
        for name in xla_reroute.ENTRY_POINTS + xla_reroute.CREATION_FUNCS:
            fn = getattr(np, name, None)
            assert not isinstance(
                fn, (xla_reroute._EntryProxy, xla_reroute._CreationProxy)
            ), name
        assert isinstance(np.random.rand(64, 64), np.ndarray)
    finally:
        xla_reroute.install(np)


def test_backend_init_watchdog_falls_back(monkeypatch):
    # a backend whose init blocks must degrade to host numpy within
    # BCI_XLA_INIT_TIMEOUT_S, not hang the user's script — and say so
    import time

    import jax

    monkeypatch.setattr(xla_reroute, "_backend_state", None)
    monkeypatch.setattr(xla_reroute, "_backend_platform", None)
    monkeypatch.setattr(xla_reroute, "_backend_error", None)
    monkeypatch.setenv("BCI_XLA_INIT_TIMEOUT_S", "0.2")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: time.sleep(60))
    try:
        t0 = time.monotonic()
        host = np.asarray(np.random.rand(64, 64))
        out = np.matmul(host, host)
        elapsed = time.monotonic() - t0
        assert isinstance(out, np.ndarray)
        assert elapsed < 10, elapsed
        assert xla_reroute._backend_state is False
        status = xla_reroute.backend_status()
        assert status["ok"] is False and status["platform"] is None
        assert "still blocked after 0.2s" in status["error"]
        # sticky: later calls skip the probe entirely and stay host-side
        assert isinstance(np.matmul(host, host), np.ndarray)
    finally:
        monkeypatch.undo()
        xla_reroute._backend_state = None


def test_backend_probe_success_is_cached(monkeypatch):
    monkeypatch.setattr(xla_reroute, "_backend_state", None)
    try:
        assert xla_reroute._backend_ok() is True
        assert xla_reroute._backend_state is True
        # the reroute says which backend it landed on (chip_smoke asserts
        # "tpu" here; the suite runs on the CPU backend)
        assert xla_reroute.backend_status() == {
            "probed": True, "ok": True, "platform": "cpu", "error": None,
        }
    finally:
        xla_reroute._backend_state = True


def test_backend_probe_error_is_kept(monkeypatch):
    # init that RAISES (on a chip host: the chip is held by another process)
    # falls back to host numpy too, with the reason kept for backend_status
    import jax

    def boom(*a, **k):
        raise RuntimeError("TPU is already in use")

    monkeypatch.setattr(xla_reroute, "_backend_state", None)
    monkeypatch.setattr(xla_reroute, "_backend_platform", None)
    monkeypatch.setattr(xla_reroute, "_backend_error", None)
    monkeypatch.setattr(jax, "devices", boom)
    try:
        assert xla_reroute._backend_ok() is False
        status = xla_reroute.backend_status()
        assert status["ok"] is False
        assert "already in use" in status["error"]
    finally:
        monkeypatch.undo()
        xla_reroute._backend_state = None
