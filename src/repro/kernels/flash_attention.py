"""Flash attention for the TPU in Pallas: a forward kernel and a backward of
two kernels (dK/dV, then dQ). No S×S tensor reaches HBM in either
direction.

Contract: the same as the XLA path (models/layers.blocked_attention) and
the oracle (kernels/ref.attention_ref): q (B, Sq, H, hd), k and v
(B, Skv, K, hd) with H % K == 0 (GQA and MQA fold a query head onto its KV
head in the index maps); causal, full or bidirectional-prefix masks, a
sliding window, Gemma2's logit softcap, and an absolute ``q_offset``.

Numerics: both products of each kernel take the inputs' own dtype
(bfloat16 in training) and accumulate in float32; the running max, the
denominator, the accumulators and the softmax's gradient stay in float32,
and P (dS in the backward) is cast to the input dtype for its product, as
the XLA path does. The forward also emits the row logsumexp (float32,
(B, H, Sq)), from which the backward recomputes P block by block.

Blocks that the mask removes entirely are skipped (``pl.when`` on a
block-level test drawn from the same mask rule), and their index maps are
clamped to a live block, so a skipped block is not copied in either. Blocks
that the mask keeps whole skip the element-wise mask.

Scope: training and prefill (sequence lengths that divide into blocks of at
least 128, ``choose_blocks``). Decode (Sq = 1) stays on the XLA path, where
GSPMD's sequence-sharded partial softmax already implements flash-decoding
semantics at the collective level. ``kernels/ops.flash_attention`` picks
between this kernel and the XLA path and is the only caller that sets
``interpret``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
#: the running max before any key is seen; above NEG_INF so that a fully
#: masked row gives exp(NEG_INF - M_INIT) = 0 and no NaN
M_INIT = -1e30
LANES = 128
#: contract the last dims of both operands: (m, d) x (n, d) -> (m, n)
NT = (((1,), (1,)), ((), ()))

#: (block_q, block_k) of the forward, of the dK/dV kernel and of the dQ
#: kernel, each the largest that divides the lengths. Chosen from timings on
#: a TPU v5e at GPT-2 M's shape (B 8, S 1024, H 16, hd 64); see PERF.md.
PREFERRED_BLOCKS = {"fwd": (512, 512), "dkv": (512, 512), "dq": (512, 512)}


def choose_blocks(sq: int, skv: int):
    """Block sizes for query length ``sq`` and key length ``skv``: a dict
    like ``PREFERRED_BLOCKS`` of the largest power-of-two multiples of 128
    up to the preferred size that divide each length, or None where a
    length is not a multiple of 128 (such inputs stay on the XLA path)."""
    if sq % LANES or skv % LANES:
        return None

    def fit(n, pref):
        b = pref
        while n % b:
            b //= 2
        return b

    return {name: (fit(sq, bq), fit(skv, bk))
            for name, (bq, bk) in PREFERRED_BLOCKS.items()}


@dataclasses.dataclass(frozen=True)
class _Blocks:
    """Which (q block, KV block) pairs the mask touches, from program ids.

    Spans over-approximate (a span's dead block is computed, fully masked,
    at no harm); ``unmasked`` under-approximates (only blocks the mask keeps
    whole skip the element-wise mask)."""

    kind: str
    window: int
    prefix_len: int
    q_offset: int
    block_q: int
    block_k: int
    nq: int
    nk: int

    @property
    def prefix(self) -> int:
        return self.prefix_len if self.kind == "prefix" else 0

    def kv_span(self, qi):
        """First and last KV block that q block ``qi`` attends to."""
        if self.kind == "full":
            return 0, self.nk - 1
        q0 = self.q_offset + qi * self.block_q
        q1 = q0 + self.block_q - 1
        hi = q1
        if self.prefix:
            hi = jnp.where(q0 < self.prefix, jnp.maximum(q1, self.prefix - 1),
                           q1)
        lo = 0
        if self.window and not self.prefix:
            lo = jnp.minimum(jnp.maximum(q0 - self.window + 1, 0)
                             // self.block_k, self.nk - 1)
        return lo, jnp.minimum(hi // self.block_k, self.nk - 1)

    def q_span(self, ki):
        """First and last q block that attends to KV block ``ki``."""
        if self.kind == "full":
            return 0, self.nq - 1
        k0 = ki * self.block_k
        k1 = k0 + self.block_k - 1
        lo = k0
        if self.prefix:
            lo = jnp.where(k0 < self.prefix, 0, k0)
        lo = jnp.clip((lo - self.q_offset) // self.block_q, 0, self.nq - 1)
        if not self.window:
            return lo, self.nq - 1
        hi = k1 + self.window - 1 - self.q_offset
        hi = jnp.clip(hi // self.block_q, 0, self.nq - 1)
        if self.prefix:
            hi = jnp.where(k0 < self.prefix, self.nq - 1, hi)
        return lo, hi

    def unmasked(self, qi, ki):
        """True where the mask keeps every pair of the block."""
        if self.kind == "full":
            return True
        q0 = self.q_offset + qi * self.block_q
        k0 = ki * self.block_k
        whole = k0 + self.block_k - 1 <= q0
        if self.window:
            whole = whole & (q0 + self.block_q - 1 - k0 < self.window)
        return whole

    def mask(self, qi, ki, shape, q_axis):
        """Element mask of block (qi, ki) of ``shape``, with query positions
        along ``q_axis`` (0: (bq, bk) scores; 1: (bk, bq) transposed)."""
        q_pos = (self.q_offset + qi * self.block_q
                 + lax.broadcasted_iota(jnp.int32, shape, q_axis))
        kv_pos = ki * self.block_k + lax.broadcasted_iota(jnp.int32, shape,
                                                          1 - q_axis)
        m = kv_pos <= q_pos
        if self.prefix:
            m = m | ((q_pos < self.prefix) & (kv_pos < self.prefix))
        if self.window:
            w_ok = (q_pos - kv_pos) < self.window
            if self.prefix:
                w_ok = w_ok | (kv_pos < self.prefix)
            m = m & w_ok
        return m


def _live(span, i):
    lo, hi = span
    return (i >= lo) & (i <= hi)


def _run_block(live, unmasked, body):
    """``body(masked)`` on a live block: without the element mask where the
    mask keeps the whole block."""
    if unmasked is True:
        pl.when(live)(lambda: body(False))
        return
    pl.when(live & unmasked)(lambda: body(False))
    pl.when(live & jnp.logical_not(unmasked))(lambda: body(True))


def _scaled(x, scale):
    """q times the softmax scale, in f32, back in q's dtype (the XLA path
    scales in f32 before its products round to the MXU's input)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _softcapped(s, softcap):
    """(capped scores, tanh for the backward's derivative)."""
    if softcap <= 0.0:
        return s, None
    t = jnp.tanh(s / softcap)
    return softcap * t, t


def _widen(x, n):
    """A row statistic kept lane-replicated, (rows, 128), to (rows, n). The
    statistics are kept so because a (rows, 1) column is broadcast across
    lanes at every use, which took a third of the forward's time on a
    v5e (PERF.md)."""
    reps = -(-n // LANES)
    if reps > 1:
        x = jnp.tile(x, (1, reps))
    return x if n == x.shape[1] else x[:, :n]


def _lanes(row):
    """A (1, n) row to its lane-replicated (n, 128) column form."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _vmem(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _fold(x):
    """(B, S, N, hd) -> (B*N, S, hd)."""
    B, S, N, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, S, hd)


def _unfold(x, B):
    """(B*N, S, hd) -> (B, S, N, hd)."""
    BN, S, hd = x.shape
    return x.reshape(B, BN // B, S, hd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, blocks: _Blocks, scale, softcap):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(masked):
        q = _scaled(q_ref[0], scale)
        v = v_ref[0]
        s = lax.dot_general(q, k_ref[0], NT, preferred_element_type=jnp.float32)
        s, _ = _softcapped(s, softcap)
        if masked:
            s = jnp.where(blocks.mask(qi, ki, s.shape, 0), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _widen(m_new, s.shape[1]))
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _widen(corr, acc_scr.shape[1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _run_block(_live(blocks.kv_span(qi), ki), blocks.unmasked(qi, ki), update)

    @pl.when(ki == blocks.nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _widen(denom, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(denom)).T[:1]


def flash_attention_fwd(q, k, v, *, interpret: bool, scale: float,
                        softcap: float = 0.0, kind: str = "causal",
                        window: int = 0, prefix_len: int = 0,
                        q_offset: int = 0, block_q: int = 128,
                        block_k: int = 128):
    """Returns o (B, Sq, H, hd) in q's dtype and the row logsumexp
    (B, H, Sq) in float32."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    blocks = _Blocks(kind, window, prefix_len, q_offset, block_q, block_k,
                     Sq // block_q, Skv // block_k)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        lo, hi = blocks.kv_span(qi)
        return ((bh // H) * K + (bh % H) // G, jnp.clip(ki, lo, hi), 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    kernel = functools.partial(_fwd_kernel, blocks=blocks, scale=scale,
                               softcap=softcap)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B * H, blocks.nq, blocks.nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[_vmem((block_q, LANES)), _vmem((block_q, LANES)),
                        _vmem((block_q, hd))],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(_fold(q), _fold(k), _fold(v))
    return _unfold(o, B), lse.reshape(B, H, Sq)


# ---------------------------------------------------------------------------
# Backward: dK and dV over q blocks, then dQ over KV blocks.
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, blocks: _Blocks, scale, softcap, G):
    """One KV block against every q block of its G query heads. Works on
    transposed scores (bk, bq), so the row statistics enter as rows."""
    ki, g, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def update(masked):
        q = _scaled(q_ref[0], scale)
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        st = lax.dot_general(k, q, NT, preferred_element_type=jnp.float32)
        st, t = _softcapped(st, softcap)
        if masked:
            st = jnp.where(blocks.mask(qi, ki, st.shape, 1), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0])
        dv_scr[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v, do, NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0])
        if t is not None:
            dst = dst * (1.0 - t * t)
        dk_scr[...] += jnp.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    _run_block(_live(blocks.q_span(ki), qi), blocks.unmasked(qi, ki), update)

    @pl.when((g == G - 1) & (qi == blocks.nq - 1))
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_scr, lse_scr, di_scr, *, blocks: _Blocks, scale, softcap):
    """One q block against every KV block it attends to."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _lanes(lse_ref[0])
        di_scr[...] = _lanes(di_ref[0])

    def update(masked):
        q = _scaled(q_ref[0], scale)
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        s = lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)
        s, t = _softcapped(s, softcap)
        if masked:
            s = jnp.where(blocks.mask(qi, ki, s.shape, 0), s, NEG_INF)
        p = jnp.exp(s - _widen(lse_scr[...], s.shape[1]))
        dp = lax.dot_general(do, v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - _widen(di_scr[...], s.shape[1]))
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    _run_block(_live(blocks.kv_span(qi), ki), blocks.unmasked(qi, ki), update)

    @pl.when(ki == blocks.nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, interpret: bool,
                        scale: float, softcap: float = 0.0,
                        kind: str = "causal", window: int = 0,
                        prefix_len: int = 0, q_offset: int = 0,
                        blocks_dkv=(128, 128), blocks_dq=(128, 128)):
    """Gradients (dq, dk, dv) of ``flash_attention_fwd`` at cotangent
    ``do``, from its output ``o`` and logsumexp ``lse``. dk and dv sum over
    the G query heads of each KV head."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    # D = rowsum(dO * O), the softmax gradient's correction, as a row.
    di = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                    o.astype(jnp.float32)).reshape(B * H, 1, Sq)
    lse = lse.reshape(B * H, 1, Sq)
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(do)

    bq, bk = blocks_dkv
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    kb = _Blocks(kind, window, prefix_len, q_offset, bq, bk, Sq // bq,
                 Skv // bk)

    def q_head(bkh, g):
        return (bkh // K) * H + (bkh % K) * G + g

    def dkv_q_map(bkh, ki, g, qi):
        lo, hi = kb.q_span(ki)
        return (q_head(bkh, g), jnp.clip(qi, lo, hi), 0)

    def dkv_row_map(bkh, ki, g, qi):
        lo, hi = kb.q_span(ki)
        return (q_head(bkh, g), 0, jnp.clip(qi, lo, hi))

    def dkv_kv_map(bkh, ki, g, qi):
        return (bkh, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, blocks=kb, scale=scale,
                          softcap=softcap, G=G),
        grid=(B * K, kb.nk, G, kb.nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), dkv_q_map),
            pl.BlockSpec((1, bk, hd), dkv_kv_map),
            pl.BlockSpec((1, bk, hd), dkv_kv_map),
            pl.BlockSpec((1, bq, hd), dkv_q_map),
            pl.BlockSpec((1, 1, bq), dkv_row_map),
            pl.BlockSpec((1, 1, bq), dkv_row_map),
        ],
        out_specs=[pl.BlockSpec((1, bk, hd), dkv_kv_map),
                   pl.BlockSpec((1, bk, hd), dkv_kv_map)],
        out_shape=[jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        scratch_shapes=[_vmem((bk, hd)), _vmem((bk, hd))],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, di)

    bq, bk = blocks_dq
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    qb = _Blocks(kind, window, prefix_len, q_offset, bq, bk, Sq // bq,
                 Skv // bk)

    def dq_q_map(bh, qi, ki):
        return (bh, qi, 0)

    def dq_kv_map(bh, qi, ki):
        lo, hi = qb.kv_span(qi)
        return ((bh // H) * K + (bh % H) // G, jnp.clip(ki, lo, hi), 0)

    def dq_row_map(bh, qi, ki):
        return (bh, 0, qi)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, blocks=qb, scale=scale, softcap=softcap),
        grid=(B * H, qb.nq, qb.nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), dq_q_map),
            pl.BlockSpec((1, bk, hd), dq_kv_map),
            pl.BlockSpec((1, bk, hd), dq_kv_map),
            pl.BlockSpec((1, bq, hd), dq_q_map),
            pl.BlockSpec((1, 1, bq), dq_row_map),
            pl.BlockSpec((1, 1, bq), dq_row_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), dq_q_map),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[_vmem((bq, hd)), _vmem((bq, LANES)),
                        _vmem((bq, LANES))],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, di)
    return _unfold(dq, B), _unfold(dk, B), _unfold(dv, B)
