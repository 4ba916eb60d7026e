"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.nbody.kernel import nbody_pallas
from repro.kernels.nbody.ref import nbody_repulsion_ref
from repro.kernels.neighbor_force.kernel import neighbor_pallas
from repro.kernels.neighbor_force.ref import neighbor_repulsion_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@pytest.mark.parametrize("n,block", [(128, 128), (256, 128), (512, 256)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_nbody_kernel_sweep(n, block, dtype):
    rng = np.random.default_rng(n)
    pos = jnp.asarray(rng.random((n, 2)) * 10, dtype)
    mass = jnp.asarray(rng.random(n) + 0.5, dtype)
    vmask = jnp.asarray(rng.random(n) > 0.15)
    w = jnp.where(vmask, mass, 0.0)
    out = nbody_pallas(pos.T, jnp.concatenate([pos.T, w[None]]),
                       1.3, 0.8, 1e-2, block_rows=block, block_cols=block,
                       interpret=True).T
    ref = nbody_repulsion_ref(pos, mass, vmask, 1.3, 0.8, 1e-2)
    np.testing.assert_allclose(np.asarray(out) * np.asarray(vmask)[:, None],
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,K,block", [(128, 8, 128), (256, 32, 128),
                                       (384, 64, 128)])
def test_neighbor_kernel_sweep(n, K, block):
    rng = np.random.default_rng(K)
    pos = rng.random((n, 2)).astype(np.float32) * 5
    mass = (rng.random(n) + 0.5).astype(np.float32)
    vmask = rng.random(n) > 0.1
    nbr = rng.integers(0, n + 1, size=(n, K)).astype(np.int32)
    nmask = rng.random((n, K)) > 0.25
    w = np.where(vmask, mass, 0).astype(np.float32)
    pos_p = np.concatenate([pos, np.zeros((1, 2), np.float32)])
    w_p = np.concatenate([w, np.zeros(1, np.float32)])
    npos = pos_p[nbr]
    nw = np.where(nmask, w_p[nbr], 0).astype(np.float32)
    nbrs = np.stack([npos[..., 0].T, npos[..., 1].T, nw.T])   # [3, K, n]
    out = neighbor_pallas(jnp.asarray(pos.T[:, None, :]), jnp.asarray(nbrs),
                          1.1, 0.9, 1e-2, block_cols=block,
                          interpret=True)[:, 0].T
    ref = neighbor_repulsion_ref(jnp.asarray(pos), jnp.asarray(mass),
                                 jnp.asarray(nbr), jnp.asarray(nmask),
                                 jnp.asarray(vmask), 1.1, 0.9, 1e-2)
    np.testing.assert_allclose(np.asarray(out) * vmask[:, None],
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("op", ["nbody", "neighbor"])
def test_unaligned_n_pads_and_runs_pallas(op, monkeypatch):
    """An n that is no multiple of any block (300) pads to the kernel's
    blocks and runs the Pallas kernel — there is no oracle fallback."""
    from repro.kernels import backend
    from repro.kernels.nbody import ops as nbody_ops
    from repro.kernels.neighbor_force import ops as nf_ops
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert backend() == "interpret"
    n, K = 300, 24
    rng = np.random.default_rng(n)
    pos = jnp.asarray(rng.random((n, 2)) * 6, jnp.float32)
    mass = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    vmask = jnp.asarray(rng.random(n) > 0.1)
    calls = []
    if op == "nbody":
        monkeypatch.setattr(nbody_ops, "nbody_pallas",
                            lambda *a, **k: calls.append(1)
                            or nbody_pallas(*a, **k))
        out = nbody_ops.nbody_repulsion(pos, mass, vmask, 1.2, 0.9, 1e-2)
        ref = nbody_repulsion_ref(pos, mass, vmask, 1.2, 0.9, 1e-2)
    else:
        nbr = jnp.asarray(rng.integers(0, n + 1, (n, K)), jnp.int32)
        nmask = jnp.asarray(rng.random((n, K)) > 0.2)
        monkeypatch.setattr(nf_ops, "neighbor_pallas",
                            lambda *a, **k: calls.append(1)
                            or neighbor_pallas(*a, **k))
        out = nf_ops.neighbor_repulsion(pos, mass, nbr, nmask, vmask,
                                        1.2, 0.9, 1e-2)
        ref = neighbor_repulsion_ref(pos, mass, nbr, nmask, vmask,
                                     1.2, 0.9, 1e-2)
    assert calls, "the Pallas kernel did not run"
    assert out.shape == (n, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_unknown_kernel_backend_is_an_error(monkeypatch):
    from repro.kernels import backend
    monkeypatch.setenv("REPRO_PALLAS", "palas")
    with pytest.raises(ValueError, match="REPRO_PALLAS"):
        backend()


@pytest.mark.parametrize("B,Sq,Sk,hd,bq,bk", [
    (2, 128, 128, 64, 128, 128),
    (1, 256, 256, 64, 128, 128),
    (2, 128, 256, 32, 128, 128),   # cross/cache: Sk > Sq
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, hd, bq, bk, causal, dtype):
    if causal and Sk != Sq:
        pytest.skip("kernel causal mask assumes aligned q/k origins")
    rng = np.random.default_rng(Sq + Sk)
    q = jnp.asarray(rng.normal(size=(B, Sq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Sk, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Sk, hd)), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_gqa_wrapper_matches_model_sdpa(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.layers import _sdpa
    rng = np.random.default_rng(0)
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    o1 = flash_attention(q, k, v, causal=True)
    o2 = _sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)
