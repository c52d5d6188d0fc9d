"""Block int8 quantization kernels.

Replaces the reference's CUDA quantization library (``csrc/quantization/*`` —
block quantize/dequantize, quantized reduction for ZeRO++ qgZ, swizzled
layouts for hierarchical all-to-all, SURVEY.md §2.5). TPU design per the
EQuARX pattern (PAPERS.md): per-block absmax scales, int8 payloads, fp32
scales side tensor; collectives then ride ICI at ~1/4 the bytes and
dequantize-on-arrival.

Layout: input flattened to ``[blocks, block_size]``; one scale per block.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

BLOCK = 2048  # elements per quantization block (16 (32,128)-lanes rows of int8)


TILE_BLOCKS = 16  # quant blocks per kernel invocation (16*2048 f32 = 128 KB)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)            # [rows, 1]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[:] = q
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def _quant_sr_kernel(x_ref, u_ref, q_ref, s_ref):
    """Stochastic-rounding variant: ``floor(x/scale + u)`` with ``u~U[0,1)``
    is unbiased per element (``E[q*scale] = x``), so gradient compression
    carries no systematic rounding drift (the EQuARX argument for why int8
    reductions train clean). Zero padding stays exactly zero
    (``floor(0+u) = 0`` for ``u < 1``)."""
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.floor(x / scale + u_ref[:]), -127, 127).astype(jnp.int8)
    q_ref[:] = q
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:, :1]


def _tile_rows(nb: int) -> int:
    """Row tile for an ``[nb, block]`` operand. The TPU lowering takes a row
    tile that is a sublane multiple or the whole axis, so small inputs use
    the whole axis and large ones ``TILE_BLOCKS`` with a ``pl.cdiv`` grid:
    the ragged last tile reads padding and its out-of-range rows are
    dropped on write, which is safe because every row is independent."""
    return nb if nb <= TILE_BLOCKS else TILE_BLOCKS


def shard_layout(n: int, world: int, block: int) -> Tuple[int, int, int]:
    """(shard, shard_padded, block) for an n-element tensor split into equal
    per-rank shards: ceil-divide, pad each shard to the 128-lane quantum, and
    fall back to 128-element blocks when the padded shard doesn't hold whole
    blocks. The SINGLE source of this arithmetic — the collectives here and
    the ledger wire-bytes accounting in ``comm/compressed.py`` must agree on
    it or the reported on-wire bytes drift from what actually moves."""
    shard = -(-n // world)
    shard_p = -(-shard // 128) * 128
    if shard_p % block != 0:
        block = 128
    return shard, shard_p, block


def quantize_int8(x: jnp.ndarray, block: int = BLOCK,
                  interpret=None, *, stochastic: bool = False,
                  key=None) -> Tuple[jnp.ndarray, jnp.ndarray, tuple]:
    """-> (int8 values [nb, block], fp32 scales [nb, 128], original shape).
    Scales are lane-replicated (nb, 128) for TPU tiling; column 0 is
    authoritative. Gridded so arbitrarily large tensors stream through VMEM.

    ``stochastic=True`` rounds with uniform dither (``key`` required): each
    element rounds to a neighbouring int8 level with probability equal to its
    fractional part, making the compression unbiased — the right mode for
    gradient reductions, where nearest-rounding bias compounds over steps."""
    shape = x.shape
    n = int(np.prod(shape)) if shape else 1
    nb = -(-n // block)
    flat = jnp.pad(jnp.ravel(x).astype(jnp.float32), (0, nb * block - n))
    x2 = flat.reshape(nb, block)
    t = _tile_rows(nb)
    spec = pl.BlockSpec((t, block), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_specs = [spec, pl.BlockSpec((t, 128), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((nb, block), jnp.int8),
                 jax.ShapeDtypeStruct((nb, 128), jnp.float32)]
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        u = jax.random.uniform(key, (nb, block), jnp.float32)
        q, s = pl.pallas_call(
            _quant_sr_kernel, grid=(pl.cdiv(nb, t),), in_specs=[spec, spec],
            out_specs=out_specs, out_shape=out_shape,
            interpret=resolve_interpret(interpret),
        )(x2, u)
    else:
        q, s = pl.pallas_call(
            _quant_kernel, grid=(pl.cdiv(nb, t),), in_specs=[spec],
            out_specs=out_specs, out_shape=out_shape,
            interpret=resolve_interpret(interpret),
        )(x2)
    return q, s, shape


def dequantize_int8(q: jnp.ndarray, s: jnp.ndarray, shape, dtype=jnp.float32,
                    interpret=None) -> jnp.ndarray:
    n = int(np.prod(shape)) if shape else 1
    nb, block = q.shape
    if s.shape[-1] == 1:  # wire format carries one lane; restore tiling locally
        s = jnp.broadcast_to(s, (nb, 128))
    t = _tile_rows(nb)
    x2 = pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(nb, t),),
        in_specs=[pl.BlockSpec((t, block), lambda i: (i, 0), memory_space=pltpu.VMEM),
                  pl.BlockSpec((t, 128), lambda i: (i, 0), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((t, block), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, s)
    return x2.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Row-wise quantization (int8 KV-cache storage)
# ---------------------------------------------------------------------------


def quantize_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row absmax int8 quantization over the LAST axis: the row-wise form
    of :func:`quantize_int8`'s ``_quant_kernel`` (same absmax/127 convention)
    for tensors whose natural scale granularity is a row, not a 2048-element
    block — the KV cache stores one ``[head_dim]`` row per (page, slot, head)
    and keeps its scale alongside the pool (``inference/v2``). Plain jnp on
    purpose: the rows here are head_dim-sized (often < the 128-lane tile
    quantum), and XLA fuses the absmax/round into the surrounding KV
    scatter/gather, so a dedicated kernel would only add dispatch overhead.

    Returns ``(int8 values x.shape, fp32 scales x.shape[:-1])``.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequant_rows_tile(q: jnp.ndarray, scale: jnp.ndarray,
                      dtype=jnp.float32) -> jnp.ndarray:
    """The :func:`quantize_rows` inverse for one tile: int8 values with one
    scale per row, the scale broadcast over the last axis. This is the SINGLE
    statement of the row-dequant convention — both the XLA gather path
    (:func:`dequantize_rows`) and the Pallas paged flash-decode kernel
    (``paged_attention.paged_flash_decode``, which fuses it against the page
    tiles in VMEM) run exactly this arithmetic, so the two attention paths
    see bit-identical dequantized rows."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def dequantize_rows(q: jnp.ndarray, scale: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows`: ``q * scale`` with the scale
    broadcast over the last axis (dequant-on-gather for the int8 KV pool)."""
    return dequant_rows_tile(q, scale, dtype)


# ---------------------------------------------------------------------------
# Quantized collectives (ZeRO++ qwZ / qgZ equivalents)
# ---------------------------------------------------------------------------


def quantized_all_gather(x, axis, block: int = BLOCK, *,
                         stochastic: bool = False, key=None):
    """qwZ-style allgather: int8 payload + scales over the wire (reference
    quantized weight allgather, ``partition_parameters.py:761``
    ``CUDAQuantizer``). Call inside shard_map; returns ``[world, *x.shape]``.

    Exchanges lower through ``lax`` directly — ledger accounting (logical vs
    on-wire bytes) is the caller's job (``comm/compressed.py`` logs one
    ``quantized_all_gather`` entry per call)."""
    q, s, shape = quantize_int8(x, block, stochastic=stochastic, key=key)
    nb = q.shape[0]
    qg = jax.lax.all_gather(q, axis, axis=0, tiled=False)         # [world, nb, block]
    sg = jax.lax.all_gather(s[:, :1], axis, axis=0, tiled=False)  # [world, nb, 1] — one lane on the wire
    world = qg.shape[0]
    n = int(np.prod(shape))
    deq = dequantize_int8(qg.reshape(world * nb, block), sg.reshape(world * nb, 1),
                          (world * nb * block,))
    return deq.reshape(world, nb * block)[:, :n].reshape((world,) + tuple(shape))


def quantized_reduce_scatter(x, axis, block: int = BLOCK, *,
                             stochastic: bool = False, key=None):
    """qgZ-flavored gradient reduction: quantize the local full-size grad,
    all-to-all the int8 shards, dequantize and mean locally (reference qgZ
    quantized grad all-to-all, ``engine.py:1193``; quant_reduce.cu). The
    result is this rank's shard of the mean, fp32, ``[ceil(n/world)]``.

    Arbitrary ``x.size`` works: the flat tensor pads up to a whole number of
    equal per-rank shards, and each shard pads to the 128-lane block
    boundary; pad lanes quantize to exact zeros and the trailing zeros land
    in the LAST rank's shard tail (callers slicing the concatenated shards
    back to ``n`` drop them). Ledger accounting lives in the
    ``comm/compressed.py`` wrapper.
    """
    from ...utils.shard_map_compat import axis_size

    world = axis_size(axis)
    n = int(np.prod(x.shape))
    # block boundaries must align with shard boundaries so each rank's blocks
    # are contiguous in the [nb, block] layout; pad ragged tails up to the
    # 128-lane quantum instead of rejecting them
    shard, shard_p, block = shard_layout(n, world, block)
    flat = jnp.pad(jnp.ravel(x).astype(jnp.float32), (0, world * shard - n))
    # lay out as [world, shard_p] so the all-to-all exchanges equal shards
    parts = jnp.pad(flat.reshape(world, shard), ((0, 0), (0, shard_p - shard)))
    q, s, _ = quantize_int8(parts, block,              # [nb, block] covering all parts
                            stochastic=stochastic, key=key)
    nb_per = q.shape[0] // world
    q = q.reshape(world, nb_per, block)
    s1 = s[:, :1].reshape(world, nb_per, 1)  # one scale lane over the wire
    qt = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False)
    st = jax.lax.all_to_all(s1, axis, split_axis=0, concat_axis=0, tiled=False)
    deq = dequantize_int8(qt.reshape(world * nb_per, block),
                          st.reshape(world * nb_per, 1),
                          (world * nb_per * block,))
    deq = deq.reshape(world, nb_per * block)[:, :shard]
    return jnp.mean(deq, axis=0)


# ---------------------------------------------------------------------------
# 1-bit sign packing (the transport for compression.onebit)
# ---------------------------------------------------------------------------


def pack_signs(x: jnp.ndarray) -> jnp.ndarray:
    """Pack the sign bits of a flat fp tensor into uint8, 8 values/byte
    (reference packs with cupy ``packbits`` in
    ``runtime/comm/nccl.py:16`` ``compressed_allreduce``). Bit k of byte i
    is ``x[8*i + k] > 0``; zeros encode as negative (receivers decode bit 0
    as ``-scale``, and the 1-bit error feedback compensates).

    ``x.size`` must be a multiple of 8.
    """
    bits = (x.reshape(-1, 8) > 0).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint8)


def unpack_signs(q: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_signs`: uint8 ``[m]`` -> ``{-1,+1}`` fp32
    ``[8*m]`` (cupy ``unpackbits`` analogue)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (q[:, None] >> shifts) & jnp.uint8(1)
    return (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(-1)
