"""Block-sparse attention for TPU in Pallas.

Reference: ``deepspeed/ops/sparse_attention/`` (Triton block-sparse matmul +
softmax, ``csrc/sparse_attention/utils.cpp``) with its ``SparsityConfig``
families (Fixed, BigBird, BSLongformer). TPU-native re-design:

* sparsity is a STATIC per-head block layout ``[H, NQ, NK]`` (numpy bool) —
  known at trace time, so the kernel grid iterates a COMPACTED column list
  per (head, q-block): only the layout's nonzero KV blocks are visited, with
  trailing padding clamped onto the last valid block (DMA elided, compute
  skipped) — the paged-attention trick applied to sparsity;
* the forward is the flash online-softmax kernel over that compacted grid;
* the backward recomputes through the masked-dense XLA reference (exact, but
  O(S^2) compute — the reference's training use of sparse attention is
  BERT-era and SURVEY marks this row lowest-priority; forward-heavy serving
  is what the kernel accelerates).

Layout builders mirror the reference ``SparsityConfig`` classes (Fixed,
BigBird, BSLongformer, Variable, LocalSlidingWindow; Dense = an all-ones
layout).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# SparsityConfig-style layout builders — [H, NQ, NK] bool, numpy (static)
# ---------------------------------------------------------------------------


def fixed_layout(num_heads: int, num_blocks: int, *, num_local_blocks: int = 4,
                 num_global_blocks: int = 1) -> np.ndarray:
    """Reference ``FixedSparsityConfig``: local band + the leading blocks of
    each local window visible globally."""
    lo = np.zeros((num_blocks, num_blocks), bool)
    for i in range(num_blocks):
        start = (i // num_local_blocks) * num_local_blocks
        lo[i, start:start + num_local_blocks] = True  # local window
        for w in range(0, i + 1, num_local_blocks):   # global columns
            lo[i, w:w + num_global_blocks] = True
    return np.repeat(lo[None], num_heads, axis=0)


def bigbird_layout(num_heads: int, num_blocks: int, *,
                   num_sliding_window_blocks: int = 3,
                   num_global_blocks: int = 1,
                   num_random_blocks: int = 1, seed: int = 0) -> np.ndarray:
    """Reference ``BigBirdSparsityConfig``: window + global + per-head random."""
    rng = np.random.default_rng(seed)
    out = np.zeros((num_heads, num_blocks, num_blocks), bool)
    half = num_sliding_window_blocks // 2
    for h in range(num_heads):
        lo = out[h]
        lo[:num_global_blocks, :] = True   # global rows attend everywhere
        lo[:, :num_global_blocks] = True   # everyone attends global columns
        for i in range(num_blocks):
            lo[i, max(0, i - half): i + half + 1] = True
            if num_blocks > num_random_blocks:
                lo[i, rng.choice(num_blocks, num_random_blocks, replace=False)] = True
    return out


def bslongformer_layout(num_heads: int, num_blocks: int, *,
                        num_sliding_window_blocks: int = 3,
                        global_block_indices=(0,)) -> np.ndarray:
    """Reference ``BSLongformerSparsityConfig``: window + symmetric globals."""
    lo = np.zeros((num_blocks, num_blocks), bool)
    half = num_sliding_window_blocks // 2
    for i in range(num_blocks):
        lo[i, max(0, i - half): i + half + 1] = True
    for g in global_block_indices:
        lo[:, g] = True
        lo[g, :] = True
    return np.repeat(lo[None], num_heads, axis=0)


def variable_layout(num_heads: int, num_blocks: int, *,
                    num_random_blocks: int = 0,
                    local_window_blocks=(4,),
                    global_block_indices=(0,),
                    horizontal_global_attention: bool = False,
                    seed: int = 0) -> np.ndarray:
    """Reference ``VariableSparsityConfig``: consecutive local windows of
    VARYING widths (the last width repeats), global COLUMNS (rows too only
    with ``horizontal_global_attention``, matching the reference default),
    and optional per-head random blocks."""
    rng = np.random.default_rng(seed)
    out = np.zeros((num_heads, num_blocks, num_blocks), bool)
    # partition rows into windows of the given widths, last width repeating
    starts, widths, i = [], [], 0
    k = 0
    while i < num_blocks:
        w = local_window_blocks[min(k, len(local_window_blocks) - 1)]
        starts.append(i)
        widths.append(w)
        i += w
        k += 1
    base = np.zeros((num_blocks, num_blocks), bool)
    for s, w in zip(starts, widths):
        base[s:s + w, s:s + w] = True
    for g in global_block_indices:
        base[:, g] = True
        if horizontal_global_attention:
            base[g, :] = True
    out[:] = base[None]
    if num_random_blocks and num_blocks > num_random_blocks:
        for h in range(num_heads):  # randoms are the only per-head part
            for i in range(num_blocks):
                out[h, i, rng.choice(num_blocks, num_random_blocks,
                                     replace=False)] = True
    return out


def local_sliding_window_layout(num_heads: int, num_blocks: int, *,
                                num_sliding_window_blocks: int = 3
                                ) -> np.ndarray:
    """Reference ``LocalSlidingWindowSparsityConfig``: pure sliding window
    (= BSLongformer with no global blocks)."""
    return bslongformer_layout(
        num_heads, num_blocks,
        num_sliding_window_blocks=num_sliding_window_blocks,
        global_block_indices=())


def causal_layout(layout: np.ndarray) -> np.ndarray:
    """Intersect a layout with the block lower-triangle (blocks fully above
    the diagonal can never contribute under causal masking)."""
    nq, nk = layout.shape[1:]
    tri = np.tril(np.ones((nq, nk), bool))
    return layout & tri[None]


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _kernel(cols_ref, cnt_ref,                       # scalar prefetch
            q_ref, k_ref, v_ref, o_ref,
            acc_sc, m_sc, l_sc, *,
            causal: bool, sm_scale: float, block_q: int, block_k: int):
    h, iq, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(j < cnt_ref[h, iq])
    def _compute():
        ik = cols_ref[h, iq, j]                       # layout column (block)
        q = q_ref[0, 0]                               # [Bq, D]
        k = k_ref[0, 0]                               # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_sc[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if causal:  # a fully-masked diagonal-adjacent block must contribute 0
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_sc[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)


def _sparse_forward(q, k, v, cols, cnt, causal, sm_scale, block_q, block_k,
                    interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq = sq // block_q
    nj = cols.shape[2]

    def _kv_map(b_, h_, iq, j, cols_ref, cnt_ref):
        # clamp padded trailing slots onto the last valid column: index
        # unchanged between consecutive steps => the pipeline elides the DMA
        jj = jnp.minimum(j, jnp.maximum(cnt_ref[h_, iq] - 1, 0))
        return (b_, h_, cols_ref[h_, iq, jj], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, nq, nj),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, j, *_: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), _kv_map),
            pl.BlockSpec((1, 1, block_k, d), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, j, *_: (b_, h_, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
    )(cols, cnt, q, k, v)


# ---------------------------------------------------------------------------
# masked-dense reference (used for the backward and for parity tests)
# ---------------------------------------------------------------------------


def masked_dense_attention(q, k, v, layout, *, causal: bool, sm_scale: float,
                           block_q: int, block_k: int):
    """[B, H, S, D] attention with the block layout expanded to a dense mask."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    # expand the SMALL [H, NQ, NK] layout on device: a host-side expansion
    # would bake an O(H*S^2) bool constant into every (backward) trace
    mask = jnp.repeat(jnp.repeat(jnp.asarray(layout), block_q, axis=1),
                      block_k, axis=2)                # [H, Sq, Sk]
    if causal:
        tri = jnp.tril(jnp.ones((sq, sk), bool))
        mask = mask & tri[None]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(mask[None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[None], probs, 0.0)         # rows with no live cols -> 0
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


class _StaticLayout:
    """Hashable wrapper so the layout can ride a nondiff static argnum."""

    def __init__(self, cols, cnt, layout):
        self.cols, self.cnt, self.layout = cols, cnt, layout
        self._key = (layout.shape, layout.tobytes())

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _StaticLayout) and self._key == other._key


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _sparse(q, k, v, sl, causal, sm_scale, block_q, block_k, interpret):
    return _sparse_forward(q, k, v, sl.cols, sl.cnt, causal, sm_scale,
                           block_q, block_k, interpret)


def _sparse_fwd(q, k, v, sl, causal, sm_scale, block_q, block_k, interpret):
    return _sparse(q, k, v, sl, causal, sm_scale, block_q, block_k,
                   interpret), (q, k, v)


def _sparse_bwd(sl, causal, sm_scale, block_q, block_k, interpret, res, g):
    # exact grads through the masked-dense reference (recompute; see module
    # docstring for the tradeoff)
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: masked_dense_attention(
            q_, k_, v_, sl.layout, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k), q, k, v)
    return vjp(g)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


_LAYOUT_CACHE: dict = {}


def _compact_layout(layout: np.ndarray, causal: bool) -> "_StaticLayout":
    """Compact a static layout to per-(head, q-block) column lists.

    Memoized on the layout's content: an eager serving loop calls
    ``sparse_attention`` with the same layout every step, and the O(H·NQ²)
    compaction plus the cols/cnt device uploads are pure functions of it.
    """
    key = (layout.shape, layout.tobytes(), causal)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    if causal:
        layout = causal_layout(layout)
    h, nq, _ = layout.shape
    # compact the columns per (head, q-block); pad with the last valid column
    cnt = layout.sum(axis=2).astype(np.int32)                   # [H, NQ]
    nj = max(int(cnt.max()), 1)
    cols = np.zeros((h, nq, nj), np.int32)
    for hh in range(h):
        for i in range(nq):
            idx = np.nonzero(layout[hh, i])[0]
            if len(idx):
                cols[hh, i, :len(idx)] = idx
                cols[hh, i, len(idx):] = idx[-1]
    sl = _StaticLayout(jnp.asarray(cols), jnp.asarray(cnt), layout)
    if len(_LAYOUT_CACHE) > 64:  # bound host+device memory held by the cache
        _LAYOUT_CACHE.clear()
    _LAYOUT_CACHE[key] = sl
    return sl


def sparse_attention(q, k, v, layout: np.ndarray, *, causal: bool = True,
                     sm_scale: Optional[float] = None, block: int = 64,
                     interpret: Optional[bool] = None):
    """Block-sparse attention over ``[B, S, H, D]`` tensors.

    ``layout``: static numpy bool ``[H, S/block, S/block]`` (see the builders
    above). Only the layout's nonzero blocks are computed/DMA'd.
    """
    interpret = resolve_interpret(interpret)
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    b, sq, h, d = q.shape
    if sq % block:
        raise ValueError(f"seq {sq} must be a multiple of block {block}")
    nq = sq // block
    if layout.shape != (h, nq, nq):
        raise ValueError(f"layout shape {layout.shape} != {(h, nq, nq)}")
    layout = np.ascontiguousarray(layout.astype(bool))
    sl = _compact_layout(layout, causal)

    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))     # [B,H,S,D]
    o = _sparse(qt, kt, vt, sl, causal, float(sm_scale), block, block,
                interpret)
    return jnp.swapaxes(o, 1, 2)
