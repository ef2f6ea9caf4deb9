"""Per-kernel shape/dtype sweeps: Pallas (interpreted on CPU) vs jnp
oracles, and the backend switch that picks the kernel mode."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.models.mamba2 import ssd_chunked

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_the_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.interpret_mode() is interpret
    assert ops.interpret_mode(not interpret) is (not interpret)  # explicit wins


def test_interpret_mode_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


def test_importing_the_kernels_initializes_no_backend():
    """The switch reads the backend when a kernel is traced, never at
    import: a process must still be free to pick its platform after
    importing the package."""
    code = (
        "import repro.kernels, repro.core, repro.core.serving, repro.launch.cache\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('NO_BACKEND')\n"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join([src, os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and "NO_BACKEND" in out.stdout, out.stdout + out.stderr


@pytest.mark.parametrize("C,N", [(2, 128), (4, 3000), (8, 1024), (3, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_kernel(C, N, dtype):
    x = _arr((C, N), dtype)
    w = jnp.asarray(RNG.dirichlet([1.0] * C), jnp.float32)
    m = jnp.asarray(RNG.integers(0, 2, C), jnp.float32)
    if float(jnp.sum(m)) == 0:
        m = m.at[0].set(1.0)
    got = ops.fedavg_masked_mean(x, w, m, block_n=256)
    want = ref.fedavg_masked_mean(x, w, m)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("N,block", [(1024, 256), (5000, 1024), (256, 256), (77, 64)])
def test_quant_roundtrip(N, block):
    x = _arr((N,))
    q, s = ops.quantize(x, block=block)
    back = ops.dequantize(q, s, block=block)
    pad = (-N) % block
    qr, sr = ref.quantize_blocks(jnp.pad(x, (0, pad)), block)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr)[:N])
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # max error bounded by half a quantization step per block
    err = np.abs(np.asarray(back) - np.asarray(x))
    step = np.repeat(np.asarray(s), block)[:N]
    assert (err <= 0.51 * step + 1e-9).all()


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64), (True, 128)])
@pytest.mark.parametrize("B,H,Hkv,S,hd", [(1, 2, 1, 256, 64), (2, 4, 2, 128, 32), (1, 8, 8, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(causal, window, B, H, Hkv, S, hd, dtype):
    q = _arr((B, H, S, hd), dtype)
    k = _arr((B, Hkv, S, hd), dtype)
    v = _arr((B, Hkv, S, hd), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,P,N,Q", [(1, 32, 2, 8, 4, 8), (2, 64, 3, 16, 8, 16), (1, 128, 1, 64, 16, 32)])
def test_ssd_scan(B, S, H, P, N, Q):
    xdt = _arr((B, S, H, P), scale=0.1)
    dA = -jnp.abs(_arr((B, S, H), scale=0.1))
    Bm = _arr((B, S, N))
    Cm = _arr((B, S, N))
    y_k, st_k = ops.ssd_full(xdt, dA, Bm, Cm, chunk=Q)
    y_r, st_r = ssd_chunked(xdt, dA, Bm, Cm, Q)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_single_chunk_matches_ref_chunk():
    Q, H, P, N = 16, 2, 8, 4
    xdt = _arr((1, Q, H, P), scale=0.1)
    dA = -jnp.abs(_arr((1, Q, H), scale=0.1))
    Bm = _arr((1, Q, N))
    Cm = _arr((1, Q, N))
    y, st, dec, ec = ops.ssd_chunk_scan(xdt, dA, Bm, Cm, chunk=Q)
    y_r, st_r, dec_r = ref.ssd_chunk(xdt[0], dA[0], Bm[0], Cm[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st[0, 0]), np.asarray(st_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dec[0, 0]), np.asarray(dec_r), rtol=2e-4, atol=2e-4)


def test_fedavg_tree_and_quant_tree():
    tree = {"a": _arr((3, 4, 5)), "b": {"c": _arr((3, 7))}}
    w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    masks = {"a": jnp.ones(3), "b": {"c": jnp.asarray([1.0, 1.0, 0.0])}}
    out = ops.fedavg_tree(tree, w, masks)
    want_a = ref.fedavg_masked_mean(tree["a"].reshape(3, -1), w, masks["a"]).reshape(4, 5)
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(want_a), rtol=1e-5, atol=1e-6)
    qt = ops.quantize_tree(tree)
    back = ops.dequantize_tree(qt, tree)
    assert back["a"].shape == (3, 4, 5)


def test_pallas_attention_impl_in_model():
    """attention_impl='pallas' routes through the flash kernel and matches
    the reference path, forward AND gradients."""
    import dataclasses

    import jax

    from repro.configs import get_arch
    from repro.models import params as P
    from repro.models import transformer as T

    base = get_arch("qwen3-1.7b").reduced()
    cfg_ref = dataclasses.replace(base, n_layers=2)
    cfg_pal = dataclasses.replace(cfg_ref, attention_impl="pallas")
    tpl = T.template(cfg_ref)
    params = P.init_params(tpl, jax.random.key(0), jnp.float32)
    toks = jnp.asarray(RNG.integers(0, cfg_ref.vocab_size, (1, 128)), jnp.int32)
    batch = {"tokens": toks}
    l_ref, g_ref = jax.value_and_grad(lambda p: T.loss_fn(cfg_ref, p, batch)[0])(params)
    l_pal, g_pal = jax.value_and_grad(lambda p: T.loss_fn(cfg_pal, p, batch)[0])(params)
    np.testing.assert_allclose(float(l_ref), float(l_pal), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)


def test_pallas_ssd_impl_in_model():
    """ssm_impl='pallas' routes mamba2 through the SSD kernel: fwd + grads."""
    import dataclasses

    import jax

    from repro.configs import get_arch
    from repro.models import params as P
    from repro.models import transformer as T

    base = get_arch("mamba2-1.3b").reduced()
    cfg_ref = base
    cfg_pal = dataclasses.replace(base, ssm_impl="pallas")
    tpl = T.template(cfg_ref)
    params = P.init_params(tpl, jax.random.key(0), jnp.float32)
    toks = jnp.asarray(RNG.integers(0, cfg_ref.vocab_size, (1, 32)), jnp.int32)
    batch = {"tokens": toks}
    l_ref, g_ref = jax.value_and_grad(lambda p: T.loss_fn(cfg_ref, p, batch)[0])(params)
    l_pal, g_pal = jax.value_and_grad(lambda p: T.loss_fn(cfg_pal, p, batch)[0])(params)
    np.testing.assert_allclose(float(l_ref), float(l_pal), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)
