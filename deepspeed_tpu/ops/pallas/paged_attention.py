"""Paged (blocked-KV) attention for TPU in Pallas.

TPU-native replacement for the reference FastGen ragged attention kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — ``blocked_flash``,
``atom_builder``; ~4.5k LoC CUDA/CUTLASS). One kernel serves both SplitFuse
prompt chunks and single-token decode:

* the grid is ``(seqs, max_blocks)`` with the KV *physical* page resolved
  per grid step through a scalar-prefetched block table — the Pallas
  pipeline DMAs one ``[kv_heads, block_size, D]`` page group (all kv heads
  of one page, contiguous in the head-major pool) per step; a static
  in-kernel loop then runs one online-softmax update per kv head;
* invalid trailing pages (``page >= ceil(kv_len/bs)``) are clamped by the
  index map onto the last valid page, so consecutive grid steps see the same
  block index and the pipeline elides the copy (near-zero HBM cost for
  short sequences in a long-table batch);
* GQA is handled in-kernel: the query tile rows for kv-head ``h`` are the
  ``group_size`` query heads sharing it — no ``jnp.repeat`` of K/V
  (contrast ``flash_attention.py``'s training path);
* chunk queries are contiguous positions ``start_pos + i`` (the SplitFuse
  packing invariant), so causal masking needs only per-sequence scalars.

Online softmax (running max / sum / fp32 accumulator in VMEM scratch across
the page dimension) follows the same scheme as ``flash_attention.py``.

Beside the prefill/packed kernel lives :func:`paged_flash_decode`, the
decode-specialized variant (one query row per sequence): it reads the
RESIDENT ``[L, N, Hk, bs, D]`` pool in place — the layer is baked into the
index map, so no per-layer ``[N, ...]`` slice of the pool ever materializes
per call — and fuses the int8 KV dequant (per-(page, slot, head)-row scales,
``quant.py`` ``quantize_rows`` convention) into the page tiles in VMEM, so
quantized pools never round-trip a full-precision copy through HBM.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret
from .quant import dequant_rows_tile

NEG_INF = -1e30


def _kernel(bt_ref, kvl_ref, start_ref, chunk_ref,   # scalar prefetch
            q_ref, k_ref, v_ref, o_ref, *rest,
            block_size: int, group: int, kv_heads: int, sm_scale: float,
            with_stats: bool = False):
    if with_stats:
        m_ref, l_ref, acc_sc, m_sc, l_sc = rest
    else:
        acc_sc, m_sc, l_sc = rest
    s_idx = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)
    rows_per_head = q_ref.shape[1] // kv_heads          # Q * group

    @pl.when(b == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    kv_len = kvl_ref[s_idx]
    n_valid = (kv_len + block_size - 1) // block_size

    @pl.when(b < n_valid)
    def _compute():
        # one page of ALL kv heads per grid step (single contiguous DMA);
        # static per-head loop keeps each matmul on one head's page
        slot_base = b * block_size
        for h in range(kv_heads):
            r0 = h * rows_per_head
            q = q_ref[0, r0:r0 + rows_per_head]           # [Q*G, D]
            k = k_ref[0, h]                               # [bs, D]
            v = v_ref[0, h]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            # row r of the tile is query-head (r % group) of chunk token (r // group)
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            qidx = rows // group
            pos_q = start_ref[s_idx] + qidx               # absolute position
            slot = slot_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (slot <= pos_q) & (qidx < chunk_ref[s_idx]) & (slot < kv_len)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_sc[r0:r0 + rows_per_head, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            # exact zero for masked entries (a fully-masked row would
            # otherwise contribute exp(NEG_INF - NEG_INF) = 1 to the sum)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_sc[r0:r0 + rows_per_head, :1] + jnp.sum(
                p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_sc[r0:r0 + rows_per_head] = (
                acc_sc[r0:r0 + rows_per_head] * alpha + pv)
            m_sc[r0:r0 + rows_per_head] = jnp.broadcast_to(
                m_new, (rows_per_head, m_sc.shape[1]))
            l_sc[r0:r0 + rows_per_head] = jnp.broadcast_to(
                l_new, (rows_per_head, l_sc.shape[1]))

    @pl.when(b == nb - 1)
    def _finalize():
        l = l_sc[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        if with_stats:  # raw online-softmax stats for two-way merges
            m_ref[0] = m_sc[:]
            l_ref[0] = l_sc[:]


def paged_attention(q, k_pool, v_pool, block_table, start_pos, chunk_len,
                    kv_len, *, sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    return_stats: bool = False):
    """Paged attention over one layer's KV pool.

    Args:
      q: ``[S, Q, Hq, D]`` grouped queries (SplitFuse chunk per sequence;
        query ``i`` of sequence ``s`` has absolute position
        ``start_pos[s] + i`` and is valid iff ``i < chunk_len[s]``).
      k_pool / v_pool: ``[N, Hk, bs, D]`` physical KV pages (head-major so
        one head's page is a contiguous ``[bs, D]`` tile — a single DMA).
      block_table: ``[S, B]`` int32 logical→physical page map.
      start_pos / chunk_len / kv_len: ``[S]`` int32.
    Returns ``[S, Q, Hq, D]``; rows of invalid queries are zero. With
    ``return_stats`` also returns the raw online-softmax ``(m, l)`` per row
    (``[S, Q, Hq]`` fp32) so a caller can merge this result with attention
    over another KV source (the frozen-pool decode loop does this with its
    in-window buffer).
    """
    S, Q, Hq, D = q.shape
    N, Hk, bs, _ = k_pool.shape
    B = block_table.shape[1]
    if Hq % Hk:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hk}")
    group = Hq // Hk
    interpret = resolve_interpret(interpret)
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(D)

    # [S, Q, Hk, G, D] -> [S, Hk, Q, G, D] -> [S, Hk*Q*G, D]: head-major row
    # blocks so head h's queries are rows [h*Q*G, (h+1)*Q*G).
    qt = q.reshape(S, Q, Hk, group, D).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(S, Hk * Q * group, D)

    bt = block_table.astype(jnp.int32)
    kvl = kv_len.astype(jnp.int32)

    def _kv_map(s, b, bt_ref, kvl_ref, start_ref, chunk_ref):
        # clamp invalid trailing pages onto the last valid one: the index is
        # then unchanged between consecutive steps and the DMA is elided
        n_valid = jnp.maximum((kvl_ref[s] + bs - 1) // bs, 1)
        ib = jnp.minimum(b, n_valid - 1)
        return (bt_ref[s, ib], 0, 0, 0)

    def _q_map(s, b, *_):
        return (s, 0, 0)

    rows = Hk * Q * group
    out_shapes = jax.ShapeDtypeStruct((S, rows, D), q.dtype)
    out_specs = pl.BlockSpec((1, rows, D), _q_map)
    if return_stats:
        out_shapes = (out_shapes,
                      jax.ShapeDtypeStruct((S, rows, 128), jnp.float32),
                      jax.ShapeDtypeStruct((S, rows, 128), jnp.float32))
        out_specs = (out_specs,
                     pl.BlockSpec((1, rows, 128), _q_map),
                     pl.BlockSpec((1, rows, 128), _q_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, B),
        in_specs=[
            pl.BlockSpec((1, rows, D), _q_map),
            pl.BlockSpec((1, Hk, bs, D), _kv_map),
            pl.BlockSpec((1, Hk, bs, D), _kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        functools.partial(_kernel, block_size=bs, group=group, kv_heads=Hk,
                          sm_scale=float(sm_scale), with_stats=return_stats),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        name="paged_attention",
        interpret=interpret,
    )(bt, kvl, start_pos.astype(jnp.int32), chunk_len.astype(jnp.int32),
      qt, k_pool, v_pool)

    def unrows(a):  # [S, Hk*Q*G, ...] -> [S, Q, Hq, ...]
        tail = a.shape[2:]
        a = a.reshape(S, Hk, Q, group, *tail).transpose(0, 2, 1, 3,
                                                        *range(4, 4 + len(tail)))
        return a.reshape(S, Q, Hq, *tail)

    if return_stats:
        out, m, l = res
        return unrows(out), unrows(m)[..., 0], unrows(l)[..., 0]
    return unrows(res)


# ---------------------------------------------------------------------------
# Decode-specialized kernel: resident pool, fused int8 dequant
# ---------------------------------------------------------------------------


def _decode_kernel(bt_ref, kvl_ref, pos_ref,            # scalar prefetch
                   q_ref, k_ref, v_ref, *rest,
                   block_size: int, group: int, kv_heads: int,
                   sm_scale: float, quantized: bool, with_stats: bool):
    """One query row-block per sequence over its live pages.

    The pool refs are the FULL ``[L, N, Hk, bs, D]`` stacks — the index map
    resolves (layer, physical page) per grid step, so the kernel reads the
    committed pool in place. ``quantized`` adds the per-row scale refs and
    fuses the dequant (``quant.dequant_rows_tile`` arithmetic) against each
    page tile while it sits in VMEM.
    """
    if quantized:
        ks_ref, vs_ref, *rest = rest
    if with_stats:
        o_ref, m_ref, l_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    s_idx = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(b == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    kv_len = kvl_ref[s_idx]
    n_valid = (kv_len + block_size - 1) // block_size

    @pl.when(b < n_valid)
    def _compute():
        slot_base = b * block_size
        pos_q = pos_ref[s_idx]
        for h in range(kv_heads):
            r0 = h * group
            q = q_ref[0, r0:r0 + group]                       # [G, D]
            k = k_ref[0, 0, h]                                # [bs, D]
            v = v_ref[0, 0, h]
            if quantized:
                # fused row dequant on the VMEM tile (the dequantized page
                # never exists in HBM) — THE shared convention, so the
                # kernel and the einsum gather path can never diverge
                k = dequant_rows_tile(k, ks_ref[0, 0, h], q.dtype)
                v = dequant_rows_tile(v, vs_ref[0, 0, h], q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            slot = slot_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (slot <= pos_q) & (slot < kv_len)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[r0:r0 + group, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_sc[r0:r0 + group, :1] + jnp.sum(
                p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_sc[r0:r0 + group] = acc_sc[r0:r0 + group] * alpha + pv
            m_sc[r0:r0 + group] = jnp.broadcast_to(
                m_new, (group, m_sc.shape[1]))
            l_sc[r0:r0 + group] = jnp.broadcast_to(
                l_new, (group, l_sc.shape[1]))

    @pl.when(b == nb - 1)
    def _finalize():
        l = l_sc[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / safe_l).astype(o_ref.dtype)
        if with_stats:
            m_ref[0] = m_sc[:]
            l_ref[0] = l_sc[:]


def paged_flash_decode(q, k_pool, v_pool, block_table, pos, kv_len, *,
                       layer: int = 0, sm_scale: Optional[float] = None,
                       interpret: Optional[bool] = None,
                       return_stats: bool = False):
    """Paged flash decode over a resident multi-layer KV pool.

    Args:
      q: ``[S, Hq, D]`` — one decode query per sequence (query head ``hq``
        shares kv head ``hq // group``, so rows are already head-major).
      k_pool / v_pool: ``[L, N, Hk, bs, D]`` resident pools (the WHOLE layer
        stack — ``layer`` is resolved by the index map, so no per-layer pool
        slice is ever materialized), or ``(int8 values, fp32 scales
        [L, N, Hk, bs])`` tuples for int8 storage: the per-(page, slot,
        head)-row scales ride in as a second ref and the dequant fuses into
        the kernel. A single-layer ``[N, Hk, bs, D]`` view (4-D) is also
        accepted (``layer`` then must be 0).
      block_table: ``[S, B]`` int32 logical→physical page map.
      pos: ``[S]`` int32 absolute position of each query (slot ``j`` of a
        sequence participates iff ``j <= pos`` and ``j < kv_len``).
      kv_len: ``[S]`` int32 tokens committed to the pool per sequence.
      sm_scale: logits scale; ``None`` = ``1/sqrt(D)`` (``attn_scale``
        families pass their explicit scale).
    Returns ``[S, Hq, D]``; with ``return_stats`` also the online-softmax
    ``(m, l)`` per row (``[S, Hq]`` fp32) for two-source merges (the fused
    decode loop merges with its in-window buffer).
    """
    quantized = isinstance(k_pool, tuple)
    if quantized:
        kq, ks = k_pool
        vq, vs = v_pool
    else:
        kq, vq = k_pool, v_pool
        ks = vs = None
    if kq.ndim == 4:  # single-layer view: normalize to the resident layout
        if layer != 0:
            raise ValueError("layer != 0 needs the [L, N, Hk, bs, D] pool")
        kq, vq = kq[None], vq[None]
        if quantized:
            ks, vs = ks[None], vs[None]
    L, N, Hk, bs, D = kq.shape
    S, Hq, _ = q.shape
    B = block_table.shape[1]
    if Hq % Hk:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hk}")
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} outside the pool's {L} layers")
    group = Hq // Hk
    interpret = resolve_interpret(interpret)
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(D)

    bt = block_table.astype(jnp.int32)
    kvl = kv_len.astype(jnp.int32)

    def _kv_map(s, b, bt_ref, kvl_ref, pos_ref):
        # same clamp as the prefill kernel: invalid trailing pages map onto
        # the last valid one, consecutive identical indices elide the DMA
        n_valid = jnp.maximum((kvl_ref[s] + bs - 1) // bs, 1)
        ib = jnp.minimum(b, n_valid - 1)
        return (layer, bt_ref[s, ib], 0, 0, 0)

    def _sc_map(s, b, bt_ref, kvl_ref, pos_ref):
        n_valid = jnp.maximum((kvl_ref[s] + bs - 1) // bs, 1)
        ib = jnp.minimum(b, n_valid - 1)
        return (layer, bt_ref[s, ib], 0, 0)

    def _q_map(s, b, *_):
        return (s, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hq, D), _q_map),
        pl.BlockSpec((1, 1, Hk, bs, D), _kv_map),
        pl.BlockSpec((1, 1, Hk, bs, D), _kv_map),
    ]
    out_shapes = jax.ShapeDtypeStruct((S, Hq, D), q.dtype)
    out_specs = pl.BlockSpec((1, Hq, D), _q_map)
    if return_stats:
        out_shapes = (out_shapes,
                      jax.ShapeDtypeStruct((S, Hq, 128), jnp.float32),
                      jax.ShapeDtypeStruct((S, Hq, 128), jnp.float32))
        out_specs = (out_specs,
                     pl.BlockSpec((1, Hq, 128), _q_map),
                     pl.BlockSpec((1, Hq, 128), _q_map))
    args = [bt, kvl, pos.astype(jnp.int32), q, kq, vq]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, Hk, bs), _sc_map),
                     pl.BlockSpec((1, 1, Hk, bs), _sc_map)]
        args += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, B),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, group=group,
                          kv_heads=Hk, sm_scale=float(sm_scale),
                          quantized=quantized, with_stats=return_stats),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        name="paged_flash_decode",
        interpret=interpret,
    )(*args)
    if return_stats:
        out, m, l = res
        return out, m[..., 0], l[..., 0]
    return res
