"""Pallas flash attention parity vs jnp reference (interpret mode on CPU)
— the analogue of reference tests/unit/ops golden tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import attention_core
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

B, S, H, D = 2, 256, 4, 64


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(causal):
    q, k, v = _rand((B, S, H, D), 0), _rand((B, S, H, D), 1), _rand((B, S, H, D), 2)
    ref = attention_core(q, k, v, causal=causal, impl="xla")
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_forward_multi_block():
    q, k, v = _rand((1, 512, 2, 32), 3), _rand((1, 512, 2, 32), 4), _rand((1, 512, 2, 32), 5)
    ref = attention_core(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_gqa_forward():
    q = _rand((B, S, 8, 32), 6)
    k, v = _rand((B, S, 2, 32), 7), _rand((B, S, 2, 32), 8)
    ref = attention_core(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_parity(causal):
    q, k, v = _rand((1, 128, 2, 32), 9), _rand((1, 128, 2, 32), 10), _rand((1, 128, 2, 32), 11)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_core(q, k, v, causal=causal, impl="xla") ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=5e-4, atol=5e-4,
                                   err_msg=f"grad mismatch for {name}")


def test_bf16_forward():
    q, k, v = (x.astype(jnp.bfloat16) for x in
               (_rand((1, 128, 2, 64), 12), _rand((1, 128, 2, 64), 13), _rand((1, 128, 2, 64), 14)))
    ref = attention_core(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_indivisible_seq_raises():
    q = k = v = _rand((1, 100, 2, 32), 15)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_model_attn_impl_flash():
    """TransformerLM with attn_impl='flash' runs and matches xla impl."""
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM, init_params

    kw = dict(vocab_size=64, hidden_size=64, intermediate_size=96, num_layers=1,
              num_heads=4, max_seq_len=128, dtype=jnp.float32)
    m_x = TransformerLM(TransformerConfig(attn_impl="xla", **kw))
    m_f = TransformerLM(TransformerConfig(attn_impl="flash", **kw))
    params = init_params(m_x, seq=128)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 128)), jnp.int32)
    lx = m_x.apply({"params": params}, toks)
    lf = m_f.apply({"params": params}, toks)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lf), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_heads", [2, 1], ids=["heads_shard", "gqa_kv_replicated"])
def test_flash_on_a_mesh_runs_per_shard(kv_heads, monkeypatch):
    """On a multi-device mesh the kernel runs inside a shard_map (GSPMD
    cannot partition a Mosaic kernel): batch over dp, heads over tp, and
    with kv heads that do not divide tp neither q nor kv heads shard. Values
    and gradients match the XLA attention either way."""
    import deepspeed_tpu.parallel.topology as topology_mod
    from deepspeed_tpu.parallel import Topology, TopologySpec

    monkeypatch.setattr(topology_mod, "_TOPOLOGY",
                        Topology(TopologySpec(tp=2)))   # dp 4 x tp 2
    q = _rand((4, 128, 4, 32), 40)
    k, v = _rand((4, 128, kv_heads, 32), 41), _rand((4, 128, kv_heads, 32), 42)

    def loss(impl):
        return lambda q, k, v: jnp.sum(jnp.sin(attention_core(
            q, k, v, causal=True, impl=impl)))

    (lf, gf), (lx, gx) = (jax.jit(jax.value_and_grad(loss(i), (0, 1, 2)))(
        q, k, v) for i in ("flash", "xla"))
    np.testing.assert_allclose(float(lf), float(lx), rtol=1e-4)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# r3 hardening: the TPU-compiled bench configuration (512x512 bf16 blocks)
# and in-kernel GQA (fwd + bwd, no kv repeat) get interpret-mode coverage
# ---------------------------------------------------------------------------


def test_block512_bf16_parity():
    """The exact bench kernel shape: 512-token blocks, bf16 inputs (r2's MFU
    path had no test at its production block size/dtype)."""
    q, k, v = (x.astype(jnp.bfloat16) for x in
               (_rand((1, 512, 2, 64), 16), _rand((1, 512, 2, 64), 17),
                _rand((1, 512, 2, 64), 18)))
    ref = attention_core(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True, block_q=512, block_k=512)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2)


def test_block512_fp32_parity():
    q, k, v = _rand((1, 512, 2, 64), 19), _rand((1, 512, 2, 64), 20), _rand((1, 512, 2, 64), 21)
    ref = attention_core(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True, block_q=512, block_k=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# r6 hardening: sm_scale pass-through, non-512-divisible sequences, and the
# no-repeat GQA XLA path + explicit flash-ineligible fallback
# ---------------------------------------------------------------------------


def _repeat_ref(q, k, v, **kw):
    """The pre-r6 XLA reference: kv heads repeat-materialized to H."""
    rep = q.shape[2] // k.shape[2]
    return attention_core(q, jnp.repeat(k, rep, axis=2),
                          jnp.repeat(v, rep, axis=2), impl="xla", **kw)


def test_seq640_gqa_smscale_fwd_bwd():
    """The ISSUE-named shape: seq 640 (divides 128, not the 512 default
    block), GQA 4/2, explicit sm_scale — fwd + bwd vs the XLA reference."""
    q = _rand((1, 640, 4, 32), 25)
    k, v = _rand((1, 640, 2, 32), 26), _rand((1, 640, 2, 32), 27)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, sm_scale=0.2) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_core(q, k, v, causal=True, impl="xla",
                                      scale=0.2) ** 2)

    out = flash_attention(q, k, v, causal=True, sm_scale=0.2)
    ref = attention_core(q, k, v, causal=True, impl="xla", scale=0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-3,
                                   atol=1e-3, err_msg=f"grad mismatch for {name}")


def test_attention_core_flash_takes_scale():
    """attention_core(impl='flash', scale=...) must reach the kernel (the
    r2-r5 behavior silently bailed to XLA whenever scale was set)."""
    q, k, v = _rand((1, 128, 2, 32), 28), _rand((1, 128, 2, 32), 29), _rand((1, 128, 2, 32), 30)
    got = attention_core(q, k, v, causal=True, impl="flash", scale=1.0)
    ref = attention_core(q, k, v, causal=True, impl="xla", scale=1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_gqa_xla_no_repeat_matches_repeat():
    """The grouped-einsum XLA GQA path == the old repeat-materialized path,
    incl. alibi (pre- and post-scale), windows and explicit scale."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q = _rand((2, 32, 8, 16), 31)
    k, v = _rand((2, 32, 2, 16), 32), _rand((2, 32, 2, 16), 33)
    al = alibi_slopes(8)
    for kw in ({}, {"scale": 0.3}, {"window": 8},
               {"alibi": al}, {"alibi": al, "alibi_post_scale": True},
               {"alibi": al, "window": 16, "scale": 0.5}):
        got = attention_core(q, k, v, causal=True, impl="xla", **kw)
        ref = _repeat_ref(q, k, v, causal=True, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5, err_msg=str(kw))


def test_flash_fallback_warns_once(caplog):
    """attn_impl=flash + window/alibi degrades to XLA with a one-time
    warning naming the reason — never silently."""
    import logging

    from deepspeed_tpu.models.transformer import (_FLASH_FALLBACK_WARNED,
                                                  alibi_slopes)

    _FLASH_FALLBACK_WARNED.clear()
    q = k = v = _rand((1, 64, 2, 16), 34)
    dlog = logging.getLogger("deepspeed_tpu")  # propagate=False: attach
    dlog.addHandler(caplog.handler)
    try:
        got = attention_core(q, k, v, causal=True, impl="flash", window=8)
        attention_core(q, k, v, causal=True, impl="flash", window=8)
        attention_core(q, k, v, causal=True, impl="flash",
                       alibi=alibi_slopes(2))
    finally:
        dlog.removeHandler(caplog.handler)
    ref = attention_core(q, k, v, causal=True, impl="xla", window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    msgs = [r.message for r in caplog.records if "attn_impl=flash" in r.message]
    assert len(msgs) == 2, msgs  # one per reason, not per call
    assert any("window" in m for m in msgs) and any("ALiBi" in m for m in msgs)


def test_model_attn_impl_fleet_knob():
    """TransformerLM(attn_impl='auto') defers to the training_fastpath
    fleet knob: forcing 'flash' engages the kernel on CPU (interpret) and
    matches the xla reference."""
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM, init_params)
    from deepspeed_tpu.ops.fastpath import configure_fastpath, reset_fastpath

    kw = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
              num_layers=1, num_heads=4, num_kv_heads=2, max_seq_len=128,
              dtype=jnp.float32)
    model = TransformerLM(TransformerConfig(**kw))
    params = init_params(model, seq=128)
    toks = jnp.asarray(np.random.default_rng(35).integers(0, 64, (2, 128)),
                       jnp.int32)
    ref = model.apply({"params": params}, toks)
    try:
        configure_fastpath(attn_impl="flash")
        got = model.apply({"params": params}, toks)
    finally:
        reset_fastpath()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_gqa_backward_parity():
    """GQA grads (dk/dv group-summed in the kernel wrapper) match the
    repeat-expanded XLA reference."""
    q = _rand((1, 128, 8, 32), 22)
    k, v = _rand((1, 128, 2, 32), 23), _rand((1, 128, 2, 32), 24)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64,
                                       block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_core(q, k, v, causal=True, impl="xla") ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert g_flash[1].shape == (1, 128, 2, 32)  # kv grads stay unexpanded
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=5e-4,
                                   atol=5e-4, err_msg=f"grad mismatch for {name}")
