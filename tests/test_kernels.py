"""Per-kernel validation: shape/dtype sweeps vs the ref.py pure-jnp oracle
(interpret=True executes the Pallas kernel body on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # (B, S, H, Hkv, D, block)
    (1, 128, 2, 2, 16, 64),
    (2, 128, 4, 2, 32, 64),
    (1, 256, 4, 1, 16, 128),
    (2, 64, 2, 2, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.ops import flash_attention

    b, s, h, hkv, d, blk = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    out = flash_attention(q, k, v, block_q=blk, block_k=blk, interpret=True)
    exp = ref.attention_bhsd(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2)).swapaxes(1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = [jnp.asarray(rng.normal(size=(1, 128, 2, 16)), jnp.float32)
               for _ in range(3)]
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    exp = ref.attention_bhsd(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), window=window).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_flash_attention_softcap():
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(1)
    q, k, v = [jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
               for _ in range(3)]
    out = flash_attention(q, k, v, attn_softcap=30.0, block_q=32, block_k=32,
                          interpret=True)
    exp = ref.attention_bhsd(q.swapaxes(1, 2), k.swapaxes(1, 2),
                             v.swapaxes(1, 2), softcap=30.0).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_flash_matches_model_backends():
    """pallas == chunked == naive at the model layer."""
    from repro.models.attention import self_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 16)), jnp.float32)
    o_naive = self_attention(q, k, v, backend="naive")
    o_chunk = self_attention(q, k, v, backend="chunked", q_chunk=32,
                             kv_chunk=32)
    o_pallas = self_attention(q, k, v, backend="pallas")
    np.testing.assert_allclose(np.asarray(o_naive), np.asarray(o_chunk),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_naive), np.asarray(o_pallas),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

def _wkv_inputs(b, s, h, d, seed=0):
    rng = np.random.default_rng(seed)
    r, k, v = [jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) * 0.5
               for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.85, 0.999, size=(b, s, h, d)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, d)), jnp.float32) * 0.3
    s0 = jnp.asarray(rng.normal(size=(b, h, d, d)), jnp.float32) * 0.1
    return r, k, v, w, u, s0


@pytest.mark.parametrize("shape", [(1, 64, 2, 8), (2, 128, 3, 16),
                                   (1, 96, 1, 32)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv6_kernel_sweep(shape, chunk):
    from repro.kernels.rwkv6_scan import ref
    from repro.kernels.rwkv6_scan.ops import wkv6

    b, s, h, d = shape
    r, k, v, w, u, s0 = _wkv_inputs(b, s, h, d, seed=hash(shape) % 997)
    y, s_f = wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    y_r, s_r = ref.wkv6_sequential(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_r),
                               atol=2e-4, rtol=2e-4)


def test_wkv6_chunked_ref_matches_sequential():
    from repro.kernels.rwkv6_scan import ref

    r, k, v, w, u, s0 = _wkv_inputs(2, 128, 2, 16, seed=5)
    y_c, s_c = ref.wkv6_chunked(r, k, v, w, u, s0, chunk_size=32)
    y_r, s_r = ref.wkv6_sequential(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=2e-4)


def test_wkv6_state_continuation():
    """Processing [a;b] == processing a then b with carried state."""
    from repro.kernels.rwkv6_scan import ref

    r, k, v, w, u, s0 = _wkv_inputs(1, 64, 2, 8, seed=9)
    y_all, s_all = ref.wkv6_sequential(r, k, v, w, u, s0)
    y1, s_mid = ref.wkv6_sequential(r[:, :32], k[:, :32], v[:, :32],
                                    w[:, :32], u, s0)
    y2, s_end = ref.wkv6_sequential(r[:, 32:], k[:, 32:], v[:, 32:],
                                    w[:, 32:], u, s_mid)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_all), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_end), np.asarray(s_all),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ckpt pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(4096, 512), (5000, 512), (1 << 14, 2048)])
def test_ckpt_pack_sweep(n, block):
    from repro.kernels.ckpt_pack.ops import ckpt_pack
    from repro.kernels.ckpt_pack.ref import ckpt_pack_blocks_ref

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    y, chk = ckpt_pack(x, block=block, interpret=True)
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad)).reshape(-1, block)
    y_r, chk_r = ckpt_pack_blocks_ref(xp)
    assert bool(jnp.all(y.reshape(-1, block) == y_r))
    assert bool(jnp.all(chk == chk_r.reshape(-1)))


def test_ckpt_pack_detects_corruption():
    from repro.kernels.ckpt_pack.ops import ckpt_pack

    x = jnp.arange(2048, dtype=jnp.float32)
    _, chk0 = ckpt_pack(x, block=512, interpret=True)
    x2 = x.at[100].set(123.0)
    _, chk1 = ckpt_pack(x2, block=512, interpret=True)
    assert chk0[0] != chk1[0]
    assert bool(jnp.all(chk0[1:] == chk1[1:]))


def test_ckpt_pack_row_padding_matches_reference():
    """Block counts off the kernel's row tile pad and slice back: the
    outputs keep the caller's block count and equal the reference."""
    from repro.kernels.ckpt_pack.kernel import ckpt_pack_blocks
    from repro.kernels.ckpt_pack.ref import ckpt_pack_blocks_ref

    x = jnp.asarray(np.random.default_rng(0).normal(size=(13, 256)),
                    jnp.float32)
    y, chk = ckpt_pack_blocks(x, interpret=True)
    y_r, chk_r = ckpt_pack_blocks_ref(x)
    assert y.shape == (13, 256) and chk.shape == (13, 1)
    assert bool(jnp.all(y == y_r)) and bool(jnp.all(chk == chk_r))
