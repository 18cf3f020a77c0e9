"""Mesh + ring attention on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bee_code_interpreter_tpu.parallel import auto_mesh, make_mesh, ring_attention
from bee_code_interpreter_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention_sharded,
)


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_make_mesh_shapes():
    mesh = make_mesh({"dp": 2, "tp": 4})
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.devices.shape == (2, 4)


def test_make_mesh_too_big():
    with pytest.raises(ValueError):
        make_mesh({"dp": 16, "tp": 4})


def test_auto_mesh():
    mesh = auto_mesh(8)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("dp", "sp", "tp")
    mesh2 = auto_mesh(8, sp=2)
    assert dict(zip(mesh2.axis_names, mesh2.devices.shape))["sp"] == 2


def rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype=dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"sp": 4})
    B, H, L, D = 2, 2, 64, 16
    q, k, v = (rand((B, H, L, D), i) for i in range(3))
    out = ring_attention_sharded(mesh, q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_grad_flows():
    mesh = make_mesh({"sp": 2})

    def loss(q, k, v):
        return ring_attention_sharded(mesh, q, k, v).sum()

    B, H, L, D = 1, 1, 16, 8
    q, k, v = (rand((B, H, L, D), i) for i in range(3))
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        return reference_attention(q, k, v).sum()

    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg), atol=1e-4, rtol=1e-4)


def test_ring_attention_bf16():
    mesh = make_mesh({"sp": 4})
    B, H, L, D = 1, 2, 32, 8
    q, k, v = (rand((B, H, L, D), i, jnp.bfloat16) for i in range(3))
    out = ring_attention_sharded(mesh, q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_ring_attention_inside_jit_compiles_once():
    mesh = make_mesh({"sp": 2})
    B, H, L, D = 1, 1, 16, 8
    q, k, v = (rand((B, H, L, D), i) for i in range(3))

    @jax.jit
    def fn(q, k, v):
        return ring_attention_sharded(mesh, q, k, v)

    out = fn(q, k, v)
    assert out.shape == (B, H, L, D)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_gqa_compact_kv(causal):
    # Grouped-query: the ring rotates the compact [B, KVH, L/sp, D] K/V
    # blocks (KVH/H of the ppermute bytes) and must still equal the
    # broadcast reference.
    mesh = make_mesh({"sp": 4})
    B, H, KVH, L, D = 1, 4, 2, 64, 16
    q = rand((B, H, L, D), 0)
    k = rand((B, KVH, L, D), 1)
    v = rand((B, KVH, L, D), 2)
    out = ring_attention_sharded(mesh, q, k, v, causal=causal)
    rep = H // KVH
    ref = reference_attention(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), causal=causal
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [5, 16, 33, 64, 200])
def test_ring_attention_window_matches_reference(window):
    # Sliding window in global positions across the ring: sp=4 over L=128
    # puts L_local=32, so these widths cover sub-block, exactly-one-block,
    # boundary-straddling, multi-block, and wider-than-sequence windows —
    # the skip predicate, the own-block mask, and the straddle mask all bite.
    mesh = make_mesh({"sp": 4})
    B, H, L, D = 1, 2, 128, 16
    q, k, v = (rand((B, H, L, D), i + 40) for i in range(3))
    out = ring_attention_sharded(mesh, q, k, v, causal=True, window=window)
    ref = reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_window_gqa_compact_kv():
    mesh = make_mesh({"sp": 4})
    B, H, KVH, L, D = 1, 4, 2, 128, 16
    q = rand((B, H, L, D), 50)
    k = rand((B, KVH, L, D), 51)
    v = rand((B, KVH, L, D), 52)
    out = ring_attention_sharded(mesh, q, k, v, causal=True, window=40)
    ref = reference_attention(q, k, v, causal=True, window=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 48, 100])
def test_ring_attention_flash_hops_window_matches_reference(window):
    # The flash-hop ring with a window: own block via the kernel's window
    # mask, full hops via the plain kernel, straddling hops via the
    # jax-level masked block — all merged on lse (interpreter mode here;
    # chip_smoke.py lowers the flash kernels under Mosaic on the chip).
    import functools

    mesh = make_mesh({"sp": 4})
    B, H, L, D = 1, 2, 128, 32
    q, k, v = (rand((B, H, L, D), i + 60) for i in range(3))
    spec = jax.sharding.PartitionSpec(None, None, "sp", None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention, axis_name="sp", causal=True, use_flash=True,
            window=window,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = fn(q, k, v)
    ref = reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_ring_attention_window_grads():
    # Gradients through the windowed ring — including the boundary-straddle
    # block (jax-level math inside lax.cond) and the window-skip predicate.
    mesh = make_mesh({"sp": 4})
    B, H, L, D = 1, 2, 64, 16
    q, k, v = (rand((B, H, L, D), i + 70) for i in range(3))
    window = 24  # straddles: L_local=16, so hop delta=16 is partial

    def loss(q, k, v):
        return (
            ring_attention_sharded(
                mesh, q, k, v, causal=True, window=window
            ) ** 2
        ).sum()

    def ref_loss(q, k, v):
        return (reference_attention(q, k, v, causal=True, window=window) ** 2).sum()

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-3, rtol=1e-3, err_msg=name
        )


def test_ring_window_requires_causal():
    mesh = make_mesh({"sp": 2})
    q, k, v = (rand((1, 2, 32, 16), i) for i in range(3))
    with pytest.raises(ValueError, match="window requires causal"):
        ring_attention_sharded(mesh, q, k, v, causal=False, window=8)


def test_ring_window_must_be_positive():
    # window=0 would mask every row of the own block: the einsum path used
    # to emit silent NaNs where the flash kernel raised — both now raise.
    mesh = make_mesh({"sp": 2})
    q, k, v = (rand((1, 2, 32, 16), i) for i in range(3))
    for use_flash in (False, True):
        with pytest.raises(ValueError, match="window must be >= 1"):
            ring_attention_sharded(
                mesh, q, k, v, causal=True, window=0, use_flash=use_flash
            )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_hops_match_reference(causal):
    # The Pallas-kernel-per-hop ring (TPU default) vs the dense reference —
    # exercised here in interpreter mode inside shard_map. Merging hops on
    # their log-sum-exp must be exact.
    import functools

    from bee_code_interpreter_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"sp": 4})
    B, H, L, D = 1, 2, 128, 32
    q, k, v = (rand((B, H, L, D), i + 20) for i in range(3))
    spec = jax.sharding.PartitionSpec(None, None, "sp", None)
    # check_vma=False: interpreter-mode pallas under vma checking hits a
    # jax-internal limitation (its own dynamic_slice loses the vma set); the
    # Mosaic path on real TPU does not use this interpreter.
    fn = jax.shard_map(
        functools.partial(
            ring_attention, axis_name="sp", causal=causal, use_flash=True
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = fn(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_ring_attention_flash_hops_grads():
    # Training through the flash ring: gradients flow through the hop
    # merging (real lse cotangents) and the kernel VJPs.
    import functools

    from bee_code_interpreter_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"sp": 2})
    B, H, KVH, L, D = 1, 4, 2, 64, 16
    q = rand((B, H, L, D), 30)
    k = rand((B, KVH, L, D), 31)
    v = rand((B, KVH, L, D), 32)
    spec = jax.sharding.PartitionSpec(None, None, "sp", None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss(q, k, v):
        return (fn(q, k, v) ** 2).sum()

    def ref_loss(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-3, rtol=1e-3, err_msg=name
        )
