"""Model-level convergence tier (reference ``tests/model/Megatron_GPT2/``):
train a small GPT-2 on deterministic synthetic data for hundreds of steps and
assert the loss curve against golden values checked into the repo.

The reference runs Megatron-GPT2 under several DeepSpeed configs and diffs the
curves against a known-good baseline (``tests/model/Megatron_GPT2/run_func_test.py``).
Here: one golden curve (ZeRO-0 fp32, ``GOLDEN_LOSSES``) + three variants that
must track it — ZeRO-3 (same math, different sharding: tight tolerance), bf16
mixed precision, and fp16 with dynamic loss scaling (loose tolerance, but the
end-of-training loss must land in the same basin).

Regenerate goldens after an intentional math change:
    python -m tests.model.test_convergence
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                              init_params, make_loss_fn)
from deepspeed_tpu.parallel import Topology, TopologySpec, set_topology

STEPS = 300
RECORD_EVERY = 10
GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden_gpt2_losses.json")

# Deterministic task: next-token prediction on modular arithmetic walks —
# learnable to near-zero loss, no data files needed, identical on every run.
VOCAB, SEQ, BATCH = 64, 32, 16


def _batch(step: int):
    rng = np.random.default_rng(10_000 + step)
    start = rng.integers(0, VOCAB, size=(BATCH, 1))
    stride = rng.integers(1, 4, size=(BATCH, 1))
    toks = (start + stride * np.arange(SEQ)) % VOCAB
    return {"tokens": jnp.asarray(toks, jnp.int32)}


def _gpt2_tiny(dtype):
    return TransformerConfig(vocab_size=VOCAB, hidden_size=64,
                             intermediate_size=256, num_layers=2, num_heads=4,
                             max_seq_len=SEQ, norm="layernorm",
                             activation="gelu", position="learned",
                             tie_embeddings=True, dtype=dtype)


def _train(config_extra, dtype=jnp.float32, steps=STEPS):
    set_topology(Topology(TopologySpec()))
    cfg = _gpt2_tiny(dtype)
    model = TransformerLM(cfg)
    params = init_params(model, seq=SEQ, seed=7)
    config = {"train_micro_batch_size_per_gpu": BATCH,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "scheduler": {"type": "WarmupLR",
                            "params": {"warmup_num_steps": 20,
                                       "warmup_min_lr": 0.0,
                                       "warmup_max_lr": 1e-3}},
              "gradient_clipping": 1.0, "steps_per_print": 10**9}
    config.update(config_extra)
    engine, *_ = ds.initialize(model=make_loss_fn(model),
                               model_parameters=params, config=config)
    losses = []
    for s in range(steps):
        loss = engine.train_batch(_batch(s))
        if s % RECORD_EVERY == 0:
            losses.append(float(loss))
    return losses


def _golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)["losses"]


def test_zero0_fp32_matches_golden():
    """The baseline itself must reproduce bit-for-bit-deterministic XLA math
    within float tolerance across machines."""
    losses = _train({"zero_optimization": {"stage": 0}})
    np.testing.assert_allclose(losses, _golden(), rtol=2e-3,
                               err_msg="ZeRO-0 fp32 diverged from golden curve")
    assert losses[-1] < 0.15, losses[-1]


def test_zero3_fp32_matches_golden():
    """ZeRO-3 is a sharding layout, not a math change: same curve, tight."""
    losses = _train({"zero_optimization": {"stage": 3}})
    np.testing.assert_allclose(losses, _golden(), rtol=2e-3,
                               err_msg="ZeRO-3 fp32 diverged from golden curve")


def test_bf16_tracks_golden():
    losses = _train({"zero_optimization": {"stage": 3}, "bf16": {"enabled": True}},
                    dtype=jnp.bfloat16)
    golden = np.asarray(_golden())
    got = np.asarray(losses)
    # early curve within 10%, convergence basin shared
    np.testing.assert_allclose(got[:5], golden[:5], rtol=0.10,
                               err_msg="bf16 early curve diverged")
    assert got[-1] < max(4 * golden[-1], 0.5), (got[-1], golden[-1])


def test_fp16_dynamic_tracks_golden():
    losses = _train({"zero_optimization": {"stage": 3},
                     "fp16": {"enabled": True, "initial_scale_power": 12,
                              "loss_scale_window": 100}},
                    dtype=jnp.float16)
    golden = np.asarray(_golden())
    got = np.asarray(losses)
    np.testing.assert_allclose(got[:5], golden[:5], rtol=0.10,
                               err_msg="fp16 early curve diverged")
    assert got[-1] < max(4 * golden[-1], 0.5), (got[-1], golden[-1])


def test_variants_agree_with_each_other():
    """Cross-config agreement on a shorter horizon (the reference asserts
    configs agree with the baseline run, not only with a stored file)."""
    short = 60
    z0 = _train({"zero_optimization": {"stage": 0}}, steps=short)
    z3 = _train({"zero_optimization": {"stage": 3}}, steps=short)
    np.testing.assert_allclose(z0, z3, rtol=1e-3)


def test_pipeline_agrees_with_dense():
    """The pipeline split is a layout, not a math change: the same untied
    GPT-2-tiny trained pp=4 (gpipe) for 60 steps must track
    the dense run step-for-step (reference run_func_test pipeline configs)."""
    import dataclasses

    from deepspeed_tpu.models.transformer import (stack_transformer_params,
                                                  transformer_pipeline_fns)
    from deepspeed_tpu.runtime.pipe.pipeline import (make_pipeline_loss_fn,
                                                     pipeline_param_specs)

    short = 60
    cfg = dataclasses.replace(_gpt2_tiny(jnp.float32), tie_embeddings=False,
                              num_layers=4)
    model = TransformerLM(cfg)
    base = init_params(model, seq=SEQ, seed=7)

    # dense run
    set_topology(Topology(TopologySpec()))
    engine_d, *_ = ds.initialize(
        model=make_loss_fn(model), model_parameters=base,
        config={"train_micro_batch_size_per_gpu": BATCH,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0, "steps_per_print": 10**9})
    dense = [float(engine_d.train_batch(_batch(s))) for s in range(short)]

    # pipeline run: same weights, pp=4, microbatches = 4
    try:
        topo = Topology(TopologySpec(pp=4))
        set_topology(topo)
        pparams = stack_transformer_params(base, cfg)
        e_fn, b_fn, h_fn = transformer_pipeline_fns(cfg)
        loss_fn = make_pipeline_loss_fn(e_fn, b_fn, h_fn, num_layers=4,
                                        num_stages=4, num_microbatches=4)
        engine_p, *_ = ds.initialize(
            model=loss_fn, model_parameters=pparams,
            config={"train_micro_batch_size_per_gpu": BATCH,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "pipeline": {"stages": 4}, "gradient_clipping": 1.0,
                    "steps_per_print": 10**9},
            topology=topo, param_specs=pipeline_param_specs(pparams))
        piped = [float(engine_p.train_batch(_batch(s))) for s in range(short)]
    finally:
        set_topology(Topology(TopologySpec()))
    np.testing.assert_allclose(piped, dense, rtol=2e-3,
                               err_msg="pipeline curve diverged from dense")


def test_moe_capacity_and_dropless_converge():
    """MoE convergence tier (reference Megatron MoE curve analogue): a tiny
    top-2/4-expert model on the same task must LEARN (final loss well under
    the dense golden's start) on BOTH gating paths, and the two paths must
    agree at the end — capacity dropping and dropless grouped-GEMM are the
    same math when capacity suffices."""
    from deepspeed_tpu.models.transformer import mixtral_config

    def run(dropless):
        topo = Topology(TopologySpec(ep=4))
        set_topology(topo)
        try:
            cfg = mixtral_config(
                "tiny", vocab_size=VOCAB, hidden_size=64,
                intermediate_size=128, num_layers=2, num_heads=4,
                num_kv_heads=4, max_seq_len=SEQ, num_experts=4, moe_top_k=2,
                moe_dropless=dropless, dtype=jnp.float32)
            model = TransformerLM(cfg)
            params = init_params(model, seq=SEQ, seed=7)
            engine, *_ = ds.initialize(
                model=make_loss_fn(model), model_parameters=params,
                config={"train_micro_batch_size_per_gpu": BATCH,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                        "moe": {"enabled": True, "ep_size": 4,
                                "num_experts": 4},
                        "gradient_clipping": 1.0, "steps_per_print": 10**9},
                topology=topo)
            return [float(engine.train_batch(_batch(s))) for s in range(STEPS)]
        finally:
            set_topology(Topology(TopologySpec()))

    cap = run(dropless=False)
    drop = run(dropless=True)
    for name, curve in (("capacity", cap), ("dropless", drop)):
        assert np.isfinite(curve).all(), f"{name} produced non-finite loss"
        assert curve[-1] < 0.5, f"{name} did not learn: final {curve[-1]:.3f}"
    # both paths end in the same basin (distinct step-by-step trajectories
    # are expected: token dropping perturbs early steps)
    assert abs(cap[-1] - drop[-1]) < 0.25, (cap[-1], drop[-1])


if __name__ == "__main__":
    # standalone regeneration: pin the CPU the way conftest does — the
    # golden curve is a CPU fp32 record, whatever else is attached
    jax.config.update("jax_platforms", "cpu")
    losses = _train({"zero_optimization": {"stage": 0}})
    with open(GOLDEN_PATH, "w") as f:
        json.dump({"losses": losses, "steps": STEPS,
                   "record_every": RECORD_EVERY,
                   "task": "modular arithmetic walks",
                   "config": "gpt2-tiny 2L/64h fp32 adamw lr1e-3 warmup20 clip1.0",
                   "seed_params": 7}, f, indent=2)
    print(f"wrote {GOLDEN_PATH}: final loss {losses[-1]:.4f}")
