"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached. That is the cheapest guard on what
interpret mode cannot see: a tile the TPU lowering refuses, a kernel that
needs more VMEM than it may use, a kernel GSPMD cannot partition. Nothing
runs here, so nothing is said about results or time; the chip lane
(``test_tpu_hardware.py``) and ``chip_smoke.py`` do that.

Shapes are the smoke models' real ones (GPT-2-small training, the
TinyLlama-1.1B serving pool). Skipped where the topology cannot be
described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import deepspeed_tpu.ops.pallas.interpret as interpret_mod

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, as a sharding for ``ShapeDtypeStruct`` s. The
    persistent compile cache is off meanwhile: it cannot read back what was
    compiled for a chip that is not there, and says so on every test."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """``jax.default_backend()`` is still the CPU here, which would send
    every kernel to the interpreter; these tests want the TPU lowering."""
    monkeypatch.setattr(interpret_mod, "interpret_default", lambda: False)


def _arr(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _on(chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def _kernel_count(fn, chip, *args) -> int:
    """Compile ``fn`` for the described chip; how many Pallas kernels the
    compiled program holds. Raises what the chip's compiler would raise."""
    compiled = jax.jit(fn).lower(*_on(chip, args)).compile()
    return compiled.as_text().count("tpu_custom_call")


def _flash(q, k, v):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    loss = lambda q, k, v: flash_attention(q, k, v).astype(F32).sum()
    return jax.grad(loss, (0, 1, 2))(q, k, v)


def _fused_nll(h, k, t):
    from deepspeed_tpu.ops.pallas.fused_loss import fused_vocab_nll

    return jax.grad(lambda h, k: fused_vocab_nll(h, k, t).sum(), (0, 1))(h, k)


def _paged_prefill(q, kp, vp, bt, start, chunk, kvl):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    return paged_attention(q, kp, vp, bt, start, chunk, kvl)


def _paged_decode(q, kp, vp, bt, pos, kvl):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    return paged_flash_decode(q, kp, vp, bt, pos, kvl, layer=21,
                              return_stats=True)


def _adam(g, m, v, p):
    from deepspeed_tpu.ops.pallas.fused_adam import adam_update

    return adam_update(g, m, v, p, 1e-4, 0.9, 0.999, 1e-8, 0.01, True, True,
                       jnp.int32(3))


def _quant_roundtrip(x):
    from deepspeed_tpu.ops.pallas.quant import dequantize_int8, quantize_int8

    q, s, shape = quantize_int8(x)
    return dequantize_int8(q, s, shape)


# TinyLlama-1.1B serving shapes: 8 sequences, 128-token chunks, GQA 32/4,
# head_dim 64, pages of 128 tokens, 9 pages a sequence. The decode kernel's
# resident pools keep all 22 layers but only 256 pages: at the smoke run's
# 2048 the relayout that test_decode_kernel_relayouts_... pins down no
# longer fits the chip, which is why the engine bails there.
_POOL = _arr(22, 256, 4, 128, 64)
_POOL_I8 = (_arr(22, 256, 4, 128, 64, dtype=jnp.int8),
            _arr(22, 256, 4, 128, dtype=F32))
_BT, _S = _arr(8, 9, dtype=I32), _arr(8, dtype=I32)
_DECODE_Q = _arr(8, 32, 64)

KERNELS = {
    # GPT-2-small step: batch 8 x 1024, MHA 12 x 64
    "flash_fwd_bwd_gpt2": (_flash, [_arr(8, 1024, 12, 64)] * 3, 3),
    # the TinyLlama training shape: GQA 32/4 at 2048
    "flash_fwd_bwd_gqa": (_flash, [_arr(2, 2048, 32, 64),
                                   _arr(2, 2048, 4, 64),
                                   _arr(2, 2048, 4, 64)], 3),
    "fused_vocab_nll_fwd_bwd_v32000": (
        _fused_nll, [_arr(4, 2048, 2048), _arr(2048, 32000),
                     _arr(4, 2048, dtype=I32)], 3),
    "paged_attention_prefill": (
        _paged_prefill, [_arr(8, 128, 32, 64), _arr(2048, 4, 128, 64),
                         _arr(2048, 4, 128, 64), _BT, _S, _S, _S], 1),
    "paged_flash_decode_bf16": (
        _paged_decode, [_DECODE_Q, _POOL, _POOL, _BT, _S, _S], 1),
    "paged_flash_decode_int8": (
        _paged_decode, [_DECODE_Q, _POOL_I8, _POOL_I8, _BT, _S, _S], 1),
    "adam_update_gpt2_embedding": (
        _adam, [_arr(50257, 768, dtype=F32)] * 4, 1),
    # nb = 18847 blocks has no divisor a TPU row tile may take: the
    # regression for the ragged-tile grid in ops/pallas/quant.py
    "quantize_dequantize_int8_gpt2_embedding": (
        _quant_roundtrip, [_arr(50257, 768)], 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, args, n_kernels = KERNELS[name]
    assert _kernel_count(fn, chip, *args) == n_kernels


@pytest.mark.parametrize("head_dim,relayouts", [(64, True), (128, False)])
def test_decode_kernel_relayouts_pool_below_128_lanes(chip, head_dim,
                                                      relayouts):
    """Why ``InferenceEngineV2`` keeps ``paged_flash_decode`` off models
    whose head_dim is not a 128-lane multiple on TPU: XLA:TPU holds a pool
    with narrower rows slot-minor, the kernel's operand is row-major, and
    the compiler then copies BOTH whole pools, padded to the lane width, on
    every call. At head_dim 128 the kernel reads the pool where it lies.
    When this test fails on ``(64, True)`` the bail can go."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    pool = _arr(4, 256, 4, 128, head_dim)
    pool_bytes = 4 * 256 * 4 * 128 * head_dim * 2
    compiled = jax.jit(paged_flash_decode).lower(*_on(chip, (
        _arr(8, 32, head_dim), pool, pool, _BT, _S, _S))).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if relayouts:
        assert temp >= 4 * pool_bytes   # 2 pools x 2x lane padding
    else:
        assert temp < pool_bytes // 8


def test_gpt2_small_forward_backward_compiles_with_flash(chip, monkeypatch):
    """The whole GPT-2-small forward+backward at the smoke batch, with the
    flash kernel present in the compiled program: 12 layers x (fwd, dq,
    dkdv). ``attn_impl`` and the topology are set here because the program
    asks ``jax.default_backend()`` and ``jax.devices()``, which are the CPU's
    in this process."""
    import deepspeed_tpu.parallel.topology as topology_mod
    from deepspeed_tpu.models.transformer import (TransformerLM, gpt2_config,
                                                  init_params, make_loss_fn)
    from deepspeed_tpu.parallel import Topology, TopologySpec

    monkeypatch.setattr(topology_mod, "_TOPOLOGY", Topology(
        TopologySpec(), devices=list(chip.device_set)))
    cfg = gpt2_config("small", dtype=BF16, attn_impl="flash")
    model = TransformerLM(cfg)
    params = jax.eval_shape(lambda: init_params(model, batch=1, seq=1024))
    batch = {"tokens": _arr(8, 1024, dtype=I32)}
    n = _kernel_count(jax.value_and_grad(make_loss_fn(model)), chip, params,
                      batch)
    assert n == 3 * cfg.num_layers
