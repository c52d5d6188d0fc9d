"""Fused Adam update as a Pallas kernel.

Replaces the reference's multi-tensor CUDA Adam
(``csrc/adam/multi_tensor_adam.cu`` behind ``FusedAdam``,
``deepspeed/ops/adam/fused_adam.py:18``). On TPU, XLA already fuses the
elementwise Adam chain per tensor; this kernel exists for the cases XLA's
fusion boundary hurts — very many small tensors — by updating a whole
flattened shard in fixed VMEM tiles with m/v updated in place.

Semantics match ``ops/optimizers.fused_adam`` exactly (decoupled AdamW or
classic L2, bias correction), which the parity tests assert.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

LANES = 128
SUBLANES = 8
TILE_ROWS = 512  # (512, 128) f32 tiles = 256 KB per operand in VMEM


def _adam_kernel(scalars_ref, g_ref, m_ref, v_ref, p_ref, u_ref, m_out_ref, v_out_ref, *,
                 b1, b2, eps, weight_decay, adam_w_mode, bias_correction):
    lr = scalars_ref[0]
    step = scalars_ref[1]
    g = g_ref[:]
    p = p_ref[:]
    if not adam_w_mode and weight_decay:
        g = g + weight_decay * p
    m = b1 * m_ref[:] + (1 - b1) * g
    v = b2 * v_ref[:] + (1 - b2) * g * g
    if bias_correction:
        # beta**step as exp(step*ln(beta)): Mosaic has no powf with a traced
        # exponent; beta is a positive compile-time constant so this is exact
        bc1 = 1.0 - jnp.exp(step * float(np.log(b1)))
        bc2 = 1.0 - jnp.exp(step * float(np.log(b2)))
        m_hat = m / bc1
        v_hat = v / bc2
    else:
        m_hat, v_hat = m, v
    u = -lr * m_hat / (jnp.sqrt(v_hat) + eps)
    if adam_w_mode and weight_decay:
        u = u - lr * weight_decay * p
    u_ref[:] = u
    m_out_ref[:] = m
    v_out_ref[:] = v


def adam_update(g, m, v, p, lr, b1, b2, eps, weight_decay, adam_w_mode, bias_correction,
                step, interpret=None):
    """One fused Adam update on a single tensor shard. All math fp32.
    Returns ``(update, new_m, new_v)`` shaped like the input."""
    interpret = resolve_interpret(interpret)
    shape = g.shape
    n = int(np.prod(shape)) if shape else 1
    cols = LANES
    rows = -(-n // cols)
    pad_rows = -(-rows // SUBLANES) * SUBLANES
    tile_rows = min(TILE_ROWS, pad_rows)
    # pad to full tiles so the grid is exact
    pad_rows = -(-pad_rows // tile_rows) * tile_rows

    def to2d(x):
        flat = jnp.ravel(x).astype(jnp.float32)
        flat = jnp.pad(flat, (0, pad_rows * cols - n))
        return flat.reshape(pad_rows, cols)

    g2, m2, v2 = to2d(g), to2d(m), to2d(v)
    p2 = to2d(p) if p is not None else jnp.zeros_like(g2)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(step, jnp.float32)])

    grid = (pad_rows // tile_rows,)
    tile = pl.BlockSpec((tile_rows, cols), lambda i: (i, 0), memory_space=pltpu.VMEM)
    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay, adam_w_mode=adam_w_mode,
                               bias_correction=bias_correction)
    u2, m_new, v_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((pad_rows, cols), jnp.float32)] * 3,
        interpret=interpret,
    )(scalars, g2, m2, v2, p2)

    def back(x2):
        return x2.reshape(-1)[:n].reshape(shape)

    return back(u2), back(m_new), back(v_new)
