"""Pallas fused LM loss: blockwise lm-head matmul + online-softmax NLL.

The training-loss epilogue the reference fuses in CUDA
(``csrc/transformer/softmax_kernels.cu`` + the cross-entropy epilogues,
SURVEY.md §2.5) is, on TPU, the last place the ``[B, S, V]`` logits tensor
is materialized: at 32k vocab and 2k sequence the fp32 logits are >1 GB of
HBM traffic that exists only to be logsumexp-reduced and read back once in
the backward. This kernel walks the vocab in blocks instead — each
``[Bt, E] @ [E, Bv]`` tile runs on the MXU and folds straight into the
per-token running ``(max, sumexp, target-logit)`` carried in VMEM scratch
(the flash-attention online-softmax scheme applied to the vocab axis), so
the logits never exist.

The ``custom_vjp`` boundary sits at the per-shard ``(lse, tgt)`` pair:

* forward returns the local logsumexp and the local target logit — tiny
  ``[T]`` fp32 arrays the caller combines across vocab shards with the SAME
  pmax/psum composition ``sequence/cross_entropy.py`` already uses, so the
  vocab/sequence-parallel psum structure is preserved;
* backward receives ``(g_lse, g_tgt)`` — the chain rule through that
  composition makes ``g_lse`` exactly the per-token softmax weight — and
  emits the Megatron-style ``softmax − onehot`` gradient block-by-block:
  one kernel accumulates ``dh`` over vocab blocks, one accumulates ``dk``
  over token blocks, each recomputing its logits tile flash-style.

``interpret=None`` auto-selects interpreter mode off-TPU so the parity
tests run on the CPU mesh (the ``flash_attention.py`` convention).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["fused_vocab_nll", "fused_loss_ready"]

# v5e-sized defaults: a 256x512 logits tile keeps the MXU busy while
# (block_t, E) + (E, block_v) + the fp32 scratch stay well under VMEM at
# E <= 4096. Vocab blocks halve down to the 128-lane floor for shapes that
# don't divide; the token dim pads up instead (see fused_vocab_nll).
DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512
NEG_INF = -1e30


def _fit_block_v(vloc: int, block: int) -> int:
    block = min(block, vloc)
    while block > 128 and vloc % block:
        block //= 2
    return block


def fused_loss_ready(vocab_shard: int) -> bool:
    """Structural eligibility: the vocab shard must tile into 128-lane
    blocks. Callers fall back to the XLA composition otherwise."""
    return vocab_shard >= 128 and vocab_shard % 128 == 0


# ---------------------------------------------------------------------------
# Forward: online softmax over vocab blocks + masked target-logit extraction
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, k_ref, t_ref, lse_ref, tgt_ref, m_sc, l_sc, t_sc, *,
                block_v: int):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        t_sc[:] = jnp.zeros_like(t_sc)

    # storage-dtype operands into the MXU, fp32 accumulation (flash scheme)
    h = h_ref[...]                                            # [Bt, E]
    k = k_ref[...]                                            # [E, Bv]
    s = lax.dot_general(h, k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)   # [Bt, Bv]

    # target logit: each (shard-relative) target id lives in exactly one
    # vocab block, so a masked row-sum extracts it without a gather
    t = t_ref[:, :1]                                          # [Bt, 1] int32
    cols = iv * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hit = cols == t
    t_sc[:] = t_sc[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True), t_sc.shape)

    m_prev = m_sc[:, :1]                                      # [Bt, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_new = (l_sc[:, :1] * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(iv == nv - 1)
    def _finalize():
        l = l_sc[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        # lane-replicated outputs (TPU tiling wants a 128-lane minor dim —
        # same layout as the flash kernel's logsumexp residual)
        lse_ref[...] = jnp.broadcast_to(m_sc[:, :1] + jnp.log(safe_l),
                                        lse_ref.shape)
        tgt_ref[...] = t_sc[:]


def _fwd_call(h, k, t2, block_t, block_v, interpret):
    tpad, e = h.shape
    vloc = k.shape[1]
    nt, nv = tpad // block_t, vloc // block_v
    kernel = functools.partial(_fwd_kernel, block_v=block_v)
    lse, tgt = pl.pallas_call(
        kernel,
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, e), lambda it, iv: (it, 0)),
            pl.BlockSpec((e, block_v), lambda it, iv: (0, iv)),
            pl.BlockSpec((block_t, 128), lambda it, iv: (it, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 128), lambda it, iv: (it, 0)),
            pl.BlockSpec((block_t, 128), lambda it, iv: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tpad, 128), jnp.float32),
            jax.ShapeDtypeStruct((tpad, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 128), jnp.float32),
            pltpu.VMEM((block_t, 128), jnp.float32),
            pltpu.VMEM((block_t, 128), jnp.float32),
        ],
        name="fused_vocab_nll_fwd",
        interpret=interpret,
    )(h, k, t2)
    return lse[:, 0], tgt[:, 0]


# ---------------------------------------------------------------------------
# Backward: softmax - onehot, block by block (two accumulation orders)
# ---------------------------------------------------------------------------


def _dlogits(h, k, t, lse, g_lse, g_tgt, iv, block_v):
    """The [Bt, Bv] gradient tile: ``g_lse * softmax + g_tgt * onehot`` —
    the loss's ``logz - tgt`` structure delivers ``g_tgt = -g_lse``, making
    this the Megatron ``softmax - onehot`` block."""
    s = lax.dot_general(h, k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse)
    cols = iv * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (cols == t).astype(jnp.float32)
    return g_lse * p + g_tgt * onehot


def _dh_kernel(h_ref, k_ref, t_ref, lse_ref, gl_ref, gt_ref, dh_ref, dh_sc, *,
               block_v: int):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        dh_sc[:] = jnp.zeros_like(dh_sc)

    k = k_ref[...]
    dl = _dlogits(h_ref[...], k, t_ref[:, :1], lse_ref[:, :1],
                  gl_ref[:, :1], gt_ref[:, :1], iv, block_v)
    dh_sc[:] = dh_sc[:] + lax.dot_general(
        dl.astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == nv - 1)
    def _finalize():
        dh_ref[...] = dh_sc[:].astype(dh_ref.dtype)


def _dk_kernel(h_ref, k_ref, t_ref, lse_ref, gl_ref, gt_ref, dk_ref, dk_sc, *,
               block_v: int):
    iv, it = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(it == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)

    h = h_ref[...]
    dl = _dlogits(h, k_ref[...], t_ref[:, :1], lse_ref[:, :1],
                  gl_ref[:, :1], gt_ref[:, :1], iv, block_v)
    dk_sc[:] = dk_sc[:] + lax.dot_general(
        h, dl.astype(h.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(it == nt - 1)
    def _finalize():
        dk_ref[...] = dk_sc[:].astype(dk_ref.dtype)


def _bwd_call(h, k, t2, lse1, g_lse, g_tgt, block_t, block_v, interpret):
    tpad, e = h.shape
    vloc = k.shape[1]
    nt, nv = tpad // block_t, vloc // block_v
    rep = lambda a: jnp.broadcast_to(a[:, None].astype(jnp.float32),
                                     (tpad, 128))
    lse2, gl2, gt2 = rep(lse1), rep(g_lse), rep(g_tgt)
    row = lambda spec_iv=False: pl.BlockSpec((block_t, 128),
                                             (lambda iv, it: (it, 0))
                                             if spec_iv else
                                             (lambda it, iv: (it, 0)))
    dh, = pl.pallas_call(
        functools.partial(_dh_kernel, block_v=block_v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, e), lambda it, iv: (it, 0)),
            pl.BlockSpec((e, block_v), lambda it, iv: (0, iv)),
            row(), row(), row(), row(),
        ],
        out_specs=[pl.BlockSpec((block_t, e), lambda it, iv: (it, 0))],
        out_shape=[jax.ShapeDtypeStruct((tpad, e), h.dtype)],
        scratch_shapes=[pltpu.VMEM((block_t, e), jnp.float32)],
        name="fused_vocab_nll_bwd_dh",
        interpret=interpret,
    )(h, k, t2, lse2, gl2, gt2)
    dk, = pl.pallas_call(
        functools.partial(_dk_kernel, block_v=block_v),
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((block_t, e), lambda iv, it: (it, 0)),
            pl.BlockSpec((e, block_v), lambda iv, it: (0, iv)),
            row(True), row(True), row(True), row(True),
        ],
        out_specs=[pl.BlockSpec((e, block_v), lambda iv, it: (0, iv))],
        out_shape=[jax.ShapeDtypeStruct((e, vloc), k.dtype)],
        scratch_shapes=[pltpu.VMEM((e, block_v), jnp.float32)],
        name="fused_vocab_nll_bwd_dk",
        interpret=interpret,
    )(h, k, t2, lse2, gl2, gt2)
    return dh, dk


# ---------------------------------------------------------------------------
# custom_vjp at the (lse, tgt) boundary
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_nll(h, k, t2, block_t, block_v, interpret):
    return _fwd_call(h, k, t2, block_t, block_v, interpret)


def _fused_nll_fwd(h, k, t2, block_t, block_v, interpret):
    lse, tgt = _fwd_call(h, k, t2, block_t, block_v, interpret)
    return (lse, tgt), (h, k, t2, lse)


def _fused_nll_bwd(block_t, block_v, interpret, res, g):
    h, k, t2, lse = res
    g_lse, g_tgt = g
    dh, dk = _bwd_call(h, k, t2, lse, g_lse, g_tgt, block_t, block_v,
                       interpret)
    return dh, dk, np.zeros(t2.shape, jax.dtypes.float0)


_fused_nll.defvjp(_fused_nll_fwd, _fused_nll_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def fused_vocab_nll(hidden, kernel, targets, *, axis_name: Optional[str] = None,
                    z_loss: float = 0.0, block_t: int = DEFAULT_BLOCK_T,
                    block_v: int = DEFAULT_BLOCK_V,
                    interpret: Optional[bool] = None):
    """Per-token NLL of ``hidden @ kernel`` logits, logits never materialized.

    ``hidden``: ``[..., E]``; ``kernel``: ``[E, Vloc]`` (this rank's vocab
    shard when ``axis_name`` is set, the full vocab otherwise); ``targets``:
    ``[...]`` int32 GLOBAL token ids. Returns fp32 per-token loss ``[...]``,
    differentiable w.r.t. hidden and kernel.

    With ``axis_name`` the call must be inside ``shard_map``: per-shard
    ``(lse, tgt)`` combine with the same pmax/psum composition as
    ``vocab_parallel_cross_entropy`` — identical on every rank of the axis.
    The token dim pads up to a block multiple (padded rows carry zero
    cotangent, so gradients are exact); ``Vloc`` must satisfy
    :func:`fused_loss_ready` — callers fall back to the XLA path otherwise.
    """
    interpret = resolve_interpret(interpret)
    vloc = kernel.shape[-1]
    if not fused_loss_ready(vloc):
        raise ValueError(f"fused loss needs a 128-multiple vocab shard, got "
                         f"{vloc}; check fused_loss_ready() and fall back")
    bv = _fit_block_v(vloc, block_v)
    lead = hidden.shape[:-1]
    t = int(np.prod(lead)) if lead else 1
    bt = min(block_t, max(8, -(-t // 8) * 8))
    h2 = hidden.reshape(t, hidden.shape[-1])
    tg = targets.reshape(t).astype(jnp.int32)
    if axis_name is not None:
        # global ids -> shard-relative: out-of-shard targets match no block
        tg = tg - lax.axis_index(axis_name) * vloc
    tpad = -(-t // bt) * bt
    if tpad != t:
        h2 = jnp.pad(h2, ((0, tpad - t), (0, 0)))
        tg = jnp.pad(tg, (0, tpad - t), constant_values=-1)
    t2 = jnp.broadcast_to(tg[:, None], (tpad, 128))
    k2 = kernel.astype(h2.dtype)
    lse, tgt = _fused_nll(h2, k2, t2, bt, bv, interpret)
    lse, tgt = lse[:t], tgt[:t]
    if axis_name is None:
        nll = lse - tgt
        if z_loss > 0.0:
            nll = nll + z_loss * jnp.square(lse)
        return nll.reshape(lead)
    # cross-shard combine — the same psum structure as the XLA reference,
    # and the chain rule through it hands _fused_nll's bwd exactly the
    # softmax weights (g_lse = exp(lse - logz))
    m = lax.pmax(lax.stop_gradient(lse), axis_name)
    sumexp = lax.psum(jnp.exp(lse - m), axis_name)
    logz = jnp.log(sumexp) + m
    tgt = lax.psum(tgt, axis_name)
    nll = logz - tgt
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(logz)
    return nll.reshape(lead)
