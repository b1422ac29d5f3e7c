"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
kernels/ref.py, executed with interpret=True on CPU. Compiles for a TPU
are checked in test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.shard_codec import shard_decode_kernel, shard_encode_kernel
from repro.kernels.ssd import ssd_kernel
from repro.kernels.wkv6 import wkv6_kernel
from repro.models.layers import MaskSpec, _mask_block, blocked_attention

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def _rec_tol(dtype):
    """Recurrences accumulate fp32 error across chunks vs the sequential
    oracle (different summation order) — slightly looser."""
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------

ATTN_SWEEP = [
    # (B, Sq, Skv, H, K, hd, kind, window, prefix, softcap, dtype)
    (1, 128, 128, 2, 2, 32, "causal", 0, 0, 0.0, jnp.float32),
    (2, 256, 256, 4, 2, 64, "causal", 0, 0, 0.0, jnp.float32),
    (2, 256, 256, 4, 1, 64, "causal", 0, 0, 0.0, jnp.float32),  # MQA
    (1, 128, 128, 4, 4, 16, "full", 0, 0, 0.0, jnp.float32),
    (1, 256, 256, 2, 2, 32, "causal", 64, 0, 0.0, jnp.float32),  # window
    (1, 256, 256, 2, 1, 32, "prefix", 0, 32, 0.0, jnp.float32),  # vlm
    (1, 128, 128, 2, 2, 32, "causal", 0, 0, 50.0, jnp.float32),  # softcap
    (1, 256, 256, 8, 2, 64, "causal", 0, 0, 0.0, jnp.bfloat16),
    (1, 128, 512, 2, 2, 32, "full", 0, 0, 0.0, jnp.float32),  # cross Skv>Sq
]


@pytest.mark.parametrize("case", ATTN_SWEEP, ids=[str(i) for i in range(len(ATTN_SWEEP))])
def test_flash_attention_vs_ref(case):
    B, Sq, Skv, H, K, hd, kind, window, prefix, softcap, dtype = case
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32)).astype(dtype)
    k = (jax.random.normal(ks[1], (B, Skv, K, hd), jnp.float32)).astype(dtype)
    v = (jax.random.normal(ks[2], (B, Skv, K, hd), jnp.float32)).astype(dtype)
    scale = 1.0 / np.sqrt(hd)
    spec = MaskSpec(kind, window=window, prefix_len=prefix)
    out, _ = flash_attention_fwd(q, k, v, scale=scale, softcap=softcap,
                                 kind=kind, window=window, prefix_len=prefix,
                                 block_q=64, block_k=64, interpret=True)
    ref = R.attention_ref(q, k, v, spec, scale=scale, softcap=softcap,
                          is_local=True if window else None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_xla_blocked_attention_matches_ref():
    """The models' XLA online-softmax path obeys the same contract."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    spec = MaskSpec("causal", window=64)
    out = blocked_attention(q, k, v, spec, scale=0.25, kv_block=64,
                            is_local=jnp.asarray(True))
    ref = R.attention_ref(q, k, v, spec, scale=0.25, is_local=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_path():
    """ops.flash_attention is differentiable (custom_vjp: the forward kernel,
    then the dK/dV and dQ kernels) and its gradient matches the pure-XLA
    gradient."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    spec = MaskSpec("causal")

    def f_kernel(q):
        return jnp.sum(ops.flash_attention(q, k, v, spec, scale=0.2) ** 2)

    def f_xla(q):
        return jnp.sum(blocked_attention(q, k, v, spec, scale=0.2) ** 2)

    g_kernel = jax.grad(f_kernel)(q)
    g_xla = jax.grad(f_xla)(q)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_xla),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind,window,prefix", [
    ("causal", 0, 0), ("causal", 96, 0), ("prefix", 0, 96),
    ("prefix", 64, 96)], ids=["causal", "window", "prefix", "prefix-window"])
def test_flash_attention_skips_masked_blocks(kind, window, prefix):
    """Four blocks a side: the kernels compute every block the mask
    touches, and for causal and window masks only those; only blocks the
    mask keeps whole skip the element mask. The forward's output and row
    logsumexp equal the oracle's."""
    S, bs, n = 256, 64, 4
    spec = MaskSpec(kind, window=window, prefix_len=prefix)
    blocks = fa._Blocks(kind, window, prefix, 0, bs, bs, n, n)
    pos = jnp.arange(S, dtype=jnp.int32)
    m = np.asarray(_mask_block(spec, pos, pos))
    live = 0
    for qi in range(n):
        for ki in range(n):
            tile = m[qi * bs:(qi + 1) * bs, ki * bs:(ki + 1) * bs]
            by_q = bool(fa._live(blocks.kv_span(qi), ki))
            by_k = bool(fa._live(blocks.q_span(ki), qi))
            assert by_q >= tile.any() and by_k >= tile.any(), (qi, ki)
            if not (prefix and window):
                assert by_q == by_k == tile.any(), (qi, ki)
            assert not bool(blocks.unmasked(qi, ki)) or tile.all(), (qi, ki)
            live += by_q
    assert live < n * n

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    out, lse = flash_attention_fwd(q, k, v, scale=0.2, kind=kind,
                                   window=window, prefix_len=prefix,
                                   block_q=bs, block_k=bs, interpret=True)
    ref = R.attention_ref(q, k, v, spec, scale=0.2)
    s = jnp.einsum("bqhd,bjhd->bhqj", q * 0.2, jnp.repeat(k, 2, axis=2))
    ref_lse = jax.nn.logsumexp(jnp.where(m, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_tol(jnp.float32))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5, atol=1e-5)


GRAD_CASES = {
    # name: (kind, window, prefix, softcap, H, K)
    "causal": ("causal", 0, 0, 0.0, 2, 2),
    "full": ("full", 0, 0, 0.0, 2, 2),
    "prefix": ("prefix", 0, 96, 0.0, 2, 2),
    "window": ("causal", 96, 0, 0.0, 2, 2),
    "softcap": ("causal", 0, 0, 20.0, 2, 2),
    "gqa": ("causal", 0, 0, 0.0, 4, 1),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(GRAD_CASES))
def test_flash_attention_grads_match_ref_and_xla(case, dtype, monkeypatch):
    """dq, dk and dv of ops.flash_attention (the backward kernels, two
    blocks of 128 a side) against jax.grad of the oracle and of the XLA
    path, as norm-relative gaps. In bf16 the kernel and the XLA path both
    round the products' operands; either sits within 1 % of the f32
    oracle."""
    monkeypatch.setattr(fa, "PREFERRED_BLOCKS",
                        {n: (128, 128) for n in fa.PREFERRED_BLOCKS})
    kind, window, prefix, softcap, H, K = GRAD_CASES[case]
    ks = jax.random.split(KEY, 4)
    q, k, v, do = (jax.random.normal(kk, (1, 256, n, 32)).astype(dtype)
                   for kk, n in zip(ks, (H, K, K, H)))
    spec = MaskSpec(kind, window=window, prefix_len=prefix)
    kw = dict(scale=32 ** -0.5, softcap=softcap,
              is_local=True if window else None)

    def grads(attn):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, spec, **kw), q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *vjp(do))]

    got = grads(ops.flash_attention)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    for want in (grads(R.attention_ref), grads(blocked_attention)):
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            gap = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert gap < tol, (name, gap)


def test_flash_attention_selects_the_kernel_by_contract():
    """The kernel where its contract holds; the XLA path for Gemma2's traced
    per-layer window and for lengths that do not divide into blocks, with
    no error."""
    from repro.models.layers import attention_sites

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    spec = MaskSpec("causal", window=32)
    with attention_sites() as sites:
        a = ops.flash_attention(q, k, v, spec, scale=0.2, is_local=True)
        b = ops.flash_attention(q, k, v, spec, scale=0.2,
                                is_local=jnp.asarray(True))
        c = ops.flash_attention(q[:, :96], k[:, :96], v[:, :96], spec,
                                scale=0.2, is_local=True)
    assert sites == ["pallas", "xla", "xla"]
    ref = R.attention_ref(q, k, v, spec, scale=0.2, is_local=True)
    for out, want in ((a, ref), (b, ref), (c, ref[:, :96])):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# WKV6.
# ---------------------------------------------------------------------------

WKV_SWEEP = [
    # (B, S, H, hd, chunk, decay_lo, dtype)
    (1, 64, 2, 16, 16, -1.0, jnp.float32),
    (2, 128, 4, 32, 32, -0.5, jnp.float32),
    (1, 128, 2, 64, 64, -5.0, jnp.float32),  # strong decay
    (1, 96, 3, 16, 32, -1.0, jnp.float32),  # chunk > remainder handling
    (2, 128, 2, 32, 32, -1.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", WKV_SWEEP, ids=[str(i) for i in range(len(WKV_SWEEP))])
def test_wkv6_vs_ref(case):
    B, S, H, hd, chunk, decay_lo, dtype = case
    if S % min(chunk, S):
        chunk = 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32).astype(dtype)
    lw = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, hd), minval=decay_lo,
                                     maxval=0.5)).astype(jnp.float32)
    u = jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.3
    state = jax.random.normal(jax.random.fold_in(KEY, 9), (B, H, hd, hd)) * 0.1

    out, sf = wkv6_kernel(r, k, v, lw, u, state=state, chunk=chunk,
                          interpret=True)
    ref_o, ref_s = R.wkv6_ref(r, k, v, lw, u, state=state)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               **_rec_tol(dtype))
    np.testing.assert_allclose(np.asarray(sf), np.asarray(ref_s),
                               **_rec_tol(dtype))


def test_wkv6_chunked_xla_matches_ref():
    from repro.models.rwkv6 import wkv6_chunked

    ks = jax.random.split(KEY, 5)
    B, S, H, hd = 2, 96, 2, 32
    r, k, v = (jax.random.normal(ks[i], (B, S, H, hd)) for i in range(3))
    lw = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, hd), minval=-2, maxval=0.5))
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    out, sf = wkv6_chunked(r, k, v, lw, u, chunk=32)
    ref_o, ref_s = R.wkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(ref_s), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# SSD (Mamba2).
# ---------------------------------------------------------------------------

SSD_SWEEP = [
    # (B, S, H, P, N, chunk, dtype)
    (1, 64, 2, 16, 8, 16, jnp.float32),
    (2, 128, 4, 32, 16, 32, jnp.float32),
    (1, 128, 2, 64, 64, 64, jnp.float32),
    (2, 128, 2, 32, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_SWEEP, ids=[str(i) for i in range(len(SSD_SWEEP))])
def test_ssd_vs_ref(case):
    B, S, H, P, N, chunk, dtype = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) + 0.01
    A_log = jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.5)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32).astype(dtype)
    st = jax.random.normal(jax.random.fold_in(KEY, 11), (B, H, P, N)) * 0.1

    y, hf = ssd_kernel(x, dt, A_log, Bm, Cm, state=st, chunk=chunk,
                       interpret=True)
    ry, rh = R.ssd_ref(x, dt, A_log, Bm, Cm, state=st)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), **_rec_tol(dtype))
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rh), **_rec_tol(dtype))


def test_ssd_chunked_xla_matches_ref():
    from repro.models.mamba2 import ssd_chunked

    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 96, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) + 0.01
    A_log = jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, hf = ssd_chunked(x, dt, A_log, Bm, Cm, chunk=32)
    ry, rh = R.ssd_ref(x, dt, A_log, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rh), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Shard codec.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb", [1, 7, 64, 300])
def test_shard_codec_roundtrip(nb):
    x = jax.random.normal(KEY, (nb, 256), jnp.float32) * 5.0
    codes, scales = shard_encode_kernel(x, interpret=True)
    rc, rs = R.shard_codec_ref(x)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(rs), rtol=1e-6)
    back = shard_decode_kernel(codes, scales, interpret=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    per_block_bound = np.asarray(scales)[:, None] * 0.5 + 1e-6
    assert (err <= per_block_bound).all()


# ---------------------------------------------------------------------------
# Model integration: use_pallas path equals XLA path.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "rwkv6-1.6b", "zamba2-1.2b"])
def test_model_pallas_path_matches_xla(arch):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.configs.base import ShapeCell

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(KEY)
    cell = ShapeCell("smoke", 128, 2, "train")  # attention: one 128 block
    batch = model.make_batch(cell, KEY)
    l_xla, _ = model.loss_fn(params, batch, use_pallas=False)
    l_pls, _ = model.loss_fn(params, batch, use_pallas=True)
    np.testing.assert_allclose(float(l_xla), float(l_pls), rtol=2e-2, atol=2e-2)
