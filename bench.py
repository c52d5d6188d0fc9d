"""Headline benchmark: Llama-family decoder, ZeRO-3 + bf16 training MFU.

Driver metric (BASELINE.json): tokens/sec/chip + MFU for Llama-class ZeRO-3
training; target >50% MFU. On a single chip we run the largest Llama-style
model that fits one chip's training state (params + fp32 master + Adam m/v)
and report model FLOPs utilisation. MFU is a device metric: a device kind with
no peak on record in ``PEAK_FLOPS`` (the CPU included) is an error.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

TARGET_MFU = 0.50  # BASELINE.json north-star: >50% MFU

# bf16 peak FLOP/s per chip by device kind (public spec sheets)
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device kind "
                     f"{device.device_kind!r}; add it to PEAK_FLOPS with its "
                     f"source before reporting MFU on it")


def model_flops_per_token(cfg, seq: int, n_params: int) -> float:
    # 6*N for the dense matmuls (fwd+bwd) + attention term 12*L*h*S.
    # Pass MATMUL params only: the input-embedding gather is not a matmul
    # (PaLM-style accounting). The untied lm_head IS a matmul and stays
    # counted.
    return 6.0 * n_params + 12.0 * cfg.num_layers * cfg.hidden_size * seq


def comm_bandwidth():
    """Second north-star (BASELINE.json): ZeRO-3 allgather busbw over ICI.

    With >1 device, times a tiled ``all_gather`` over the mesh (the ZeRO-3
    param-gather pattern, same op as ``bin/ds_bench``). On a single chip no
    interconnect exists, so report achieved HBM copy bandwidth instead — the
    bound a 1-chip "gather" actually hits. Iterations are chained through a
    carry so XLA cannot hoist or CSE the collective, and the queue is drained
    by one host read.
    """
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices())
    n = len(devs)
    count = 64 * 2**20  # 64Mi bf16 elements = 128 MiB gathered
    count = (count // max(n, 1)) * max(n, 1)
    x = jnp.ones((count,), jnp.bfloat16)

    def make(reps):
        if n > 1:
            mesh = Mesh(devs, ("x",))

            def loop(shard):
                def body(c, _):
                    full = jax.lax.all_gather(c, "x", tiled=True)  # [count]
                    return full[: c.shape[0]] + jnp.bfloat16(1e-3), ()
                c, _ = jax.lax.scan(body, shard, None, length=reps)
                return c[0]

            from deepspeed_tpu.utils.shard_map_compat import shard_map_nocheck

            return jax.jit(shard_map_nocheck(loop, mesh, in_specs=P("x"),
                                             out_specs=P()))

        def f_body(x):
            def body(c, _):
                return c + jnp.bfloat16(1.0), ()
            c, _ = jax.lax.scan(body, x, None, length=reps)
            return c[0]
        return jax.jit(f_body)

    # difference two rep counts to cancel the fixed dispatch+sync RTT
    lo, hi = 10, 110
    f_lo, f_hi = make(lo), make(hi)
    float(f_lo(x)); float(f_hi(x))  # compile + drain
    t0 = time.perf_counter(); float(f_lo(x)); t_lo = time.perf_counter() - t0
    t0 = time.perf_counter(); float(f_hi(x)); t_hi = time.perf_counter() - t0
    dt = (t_hi - t_lo) / (hi - lo)
    nbytes = count * 2
    if n > 1:
        busbw = nbytes * (n - 1) / n / dt / 1e9
        return {"allgather_busbw_gbps": round(busbw, 1), "allgather_devices": n}
    # read + write per element
    return {"hbm_copy_gbps": round(2 * nbytes / dt / 1e9, 1), "allgather_devices": 1}


def plan_bench_config(cfg, seq: int):
    """Order (batch, remat) candidates by expected MFU, filtered by an HBM
    headroom estimate (r2 used BENCH_REMAT/BENCH_BATCH env vars instead —
    the probe makes the choice automatic; a compile-time OOM in main() still
    falls through to the next candidate)."""
    from deepspeed_tpu.models.transformer import TransformerLM, init_params

    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: init_params(model, batch=1, seq=seq))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    state_bytes = 14 * n  # bf16 params + fp32 master + fp32 adam m/v

    h, inter, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                      cfg.vocab_size)

    def act_bytes(batch, remat):
        # calibrated on v5e: batch 6 no-remat fits beside the state (r2's
        # measured operating point), batch 8 does not
        tok = batch * seq
        logits = tok * V * 4  # fp32 logits (softmax residuals are transient)
        if remat:
            return L * tok * h * 2 * 2 + logits
        per_layer = (8 * h + 2 * inter) * 2  # bf16 residuals/qkv/mlp hidden
        return L * tok * per_layer + logits

    try:
        limit = jax.local_devices()[0].memory_stats().get("bytes_limit", 16e9)
    except Exception:
        limit = 16e9
    budget = 0.92 * limit - state_bytes
    plan = [(b, r) for b, r in ((8, False), (6, False), (4, False),
                                (8, True), (6, True))
            if act_bytes(b, r) <= budget]
    if plan[-1:] != [(4, True)]:
        plan.append((4, True))  # last-resort fallback for the OOM retry loop
    return plan


def decode_bench():
    """FastGen-analogue serving number: steady-state decode tokens/sec on the
    v2 ragged engine (frozen-pool fused decode: block-table gather attention
    merged with the in-window buffer, on-device sampling; the Pallas paged
    kernel serves the prefill chunks).
    The reference's headline is serving throughput (blogs/deepspeed-fastgen);
    this measures the decode regime, the part the paged kernel owns."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config)

    cfg = llama_config("7b", num_layers=12, hidden_size=1536,
                       intermediate_size=4096, num_heads=12, num_kv_heads=4,
                       vocab_size=32000, max_seq_len=4096,
                       dtype=jnp.bfloat16)
    # 512-token pages + 32 sequences, frozen-pool fused decode (ctx grows
    # to ~1.5k over the 1024 warmup+timed steps); page 1024 exceeds scoped
    # VMEM
    n_seqs, prompt_len, kv_blocks, bs = 32, 512, 200, 512
    steps, warmup = 512, 512  # warmup compiles the same n_steps program
    dtype = "bfloat16"

    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=min(prompt_len, 128))
    # slack covers decode tokens sampled while other sequences still prefill,
    # so both decode_stream calls clamp to the same n_steps (one compile)
    slack = 64
    total_len = prompt_len + steps + warmup + slack + 1
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        token_budget=max(256, n_seqs), max_ragged_sequence_count=n_seqs,
        max_chunk_size=256, num_kv_blocks=kv_blocks, kv_block_size=bs,
        max_blocks_per_seq=-(-total_len // bs), dtype=dtype))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len).astype(np.int32)
               for _ in range(n_seqs)]
    eng.put(list(range(n_seqs)), prompts,
            max_new_tokens=steps + warmup + slack)
    while any(s.in_prefill for s in eng.state_manager.all()):
        eng.step()                       # prefill chunks + compile
    eng.decode_stream(warmup)            # fused decode warmup (own program)
    t0 = time.perf_counter()
    eng.decode_stream(steps)             # ONE dispatch, ONE host sync
    dt = time.perf_counter() - t0
    out = {"decode_tokens_per_sec": round(n_seqs * steps / dt, 1),
           "decode_seqs": n_seqs, "decode_ctx": prompt_len,
           "decode_attn": eng.decode_attn_impl}
    try:
        out.update(v1_generate_bench(cfg, model, params))
    except Exception as e:  # v1 number must not kill the v2 one
        out["v1_generate_error"] = str(e)[:200]
    return out


def v1_generate_bench(cfg, model, params):
    """v1 engine ``generate`` throughput, beside the v2 decode number."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    b, prompt, new = 16, 256, 256
    eng = InferenceEngine(model, params, DeepSpeedInferenceConfig(
        dtype="bfloat16", max_out_tokens=prompt + new + 8))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, prompt)), jnp.int32)
    eng.generate(toks, max_new_tokens=new)  # compile
    t0 = time.perf_counter()
    got = eng.generate(toks, max_new_tokens=new)
    np.asarray(got)
    dt = time.perf_counter() - t0
    return {"v1_generate_tokens_per_sec": round(b * new / dt, 1),
            "v1_generate_batch": b, "v1_generate_new": new}


def main():
    import deepspeed_tpu as ds
    import deepspeed_tpu.comm as dscomm
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config, make_loss_fn)

    dev = jax.devices()[0]
    peak = peak_flops(dev)  # before any step: no peak on record, no MFU row

    # comms ledger on before the step traces: the headline row carries the
    # per-op logical/wire byte profile like every ladder rung
    dscomm.get_comms_logger().configure(enabled=True, prof_all=True)
    dscomm.get_comms_logger().reset()

    # ~460M-param Llama shape: fits one chip with fp32 master + Adam
    # state. The remat/batch choice is PROBED (HBM headroom estimate +
    # compile-time OOM fallback), not env-fixed.
    def make_cfg(remat):
        return llama_config("7b", num_layers=12, hidden_size=1536,
                            intermediate_size=4096, num_heads=12,
                            num_kv_heads=12, vocab_size=32000,
                            max_seq_len=2048, dtype=jnp.bfloat16,
                            remat=remat,
                            remat_policy=os.environ.get("BENCH_POLICY") or None)

    seq, steps, warmup = 2048, 30, 3
    manual = {k for k in ("BENCH_BATCH", "BENCH_REMAT", "BENCH_POLICY")
              if k in os.environ}
    if manual:  # any explicit knob pins the configuration (no probe)
        plan = [(int(os.environ.get("BENCH_BATCH", "6")),
                 os.environ.get("BENCH_REMAT", "0") != "0")]
    else:
        plan = plan_bench_config(make_cfg(False), seq)

    # Pre-stage batches on device: per-step host RNG + H2D transfers would
    # serialize the async dispatch pipeline.
    engine = cfg = loss = params = None
    for pi, (batch, remat) in enumerate(plan):
        cfg = make_cfg(remat)
        model = TransformerLM(cfg)
        rng = np.random.default_rng(0)
        batches = [{"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, seq)), jnp.int32)}
            for _ in range(8)]
        try:
            params = init_params(model, batch=1, seq=seq)
            engine, *_ = ds.initialize(
                model=make_loss_fn(model), model_parameters=params,
                config={"train_micro_batch_size_per_gpu": batch,
                        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                        "zero_optimization": {"stage": 3},
                        "bf16": {"enabled": True},
                        "gradient_clipping": 1.0,
                        "steps_per_print": 10**9})
            # the train step compiles LAZILY: the warmup must run inside the
            # try so an activation-memory OOM falls through to the next plan
            for i in range(warmup):
                loss = engine.train_batch(batches[i % len(batches)])
            float(loss)  # drain the queue
            break
        except Exception as e:  # OOM: try the next plan entry
            engine = params = None  # free the failed attempt's device arrays
            if "RESOURCE_EXHAUSTED" not in str(e) or pi == len(plan) - 1:
                raise
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(engine.state.params))
    embed_params = cfg.vocab_size * cfg.hidden_size
    # input-embedding gather is not a matmul; tied head reuses the table AS
    # a matmul so it stays counted in that case
    n_matmul = n_params - (0 if cfg.tie_embeddings else embed_params)

    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = engine.train_batch(batches[i % len(batches)])
    final_loss = float(loss)  # device steps are ordered: last done => all done
    dt = time.perf_counter() - t0

    n_chips = len(jax.devices())
    tokens_per_sec = batch * seq * steps / dt / n_chips  # per-chip
    mfu = model_flops_per_token(cfg, seq, n_matmul) * tokens_per_sec / peak
    # embedding-inclusive accounting (the input-embedding gather counted in 6N)
    mfu_incl_embed = (model_flops_per_token(cfg, seq, n_params)
                      * tokens_per_sec / peak)

    ledger = dscomm.get_comms_logger().totals()
    dscomm.get_comms_logger().configure(enabled=False)

    comm = comm_bandwidth()
    try:
        decode = decode_bench()
    except Exception as e:  # decode bench must not kill the headline metric
        decode = {"decode_tokens_per_sec": None, "decode_error": str(e)[:200]}

    print(json.dumps({
        "metric": "llama_zero3_bf16_mfu",
        "value": round(mfu, 4),
        "unit": "MFU",
        "vs_baseline": round(mfu / TARGET_MFU, 4),
        "mfu_incl_embed": round(mfu_incl_embed, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "n_params": n_params,
        "batch": batch,
        "remat": cfg.remat,
        "device": getattr(dev, "device_kind", dev.platform),
        "final_loss": final_loss,
        **comm,
        **decode,
        **({"comms_ledger": ledger} if ledger else {}),
    }))


# ---------------------------------------------------------------------------
# BASELINE.md config ladder (rungs 1-5). ``bench.py --ladder`` emits one JSON
# line per rung; rungs that need a multi-device mesh run on the virtual
# 8-device CPU mesh (relative numbers: bubble fraction, dropless-vs-capacity
# ratio), rungs 2-3 need a chip with a peak on record. LADDER.json records all.
# ---------------------------------------------------------------------------


def _time_steps(engine, batches, steps, warmup):
    loss = None
    for i in range(warmup):
        loss = engine.train_batch(batches[i % len(batches)])
    float(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss = engine.train_batch(batches[i % len(batches)])
    final = float(loss)
    return time.perf_counter() - t0, final


def rung1_simple_zero0():
    """Rung 1: cifar10_deepspeed-style SimpleModel, ZeRO-0 (pure DP)."""
    import deepspeed_tpu as ds

    dim, batch, steps, warmup = 256, 512, 20, 3
    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(0, 0.05, (dim, dim)), jnp.float32),
              "b1": jnp.zeros((dim,), jnp.float32),
              "w2": jnp.asarray(rng.normal(0, 0.05, (dim, 10)), jnp.float32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        logits = h @ p["w2"]
        return jnp.mean(jax.nn.logsumexp(logits, -1)
                        - jnp.take_along_axis(logits, b["y"][:, None], 1)[:, 0])

    engine, *_ = ds.initialize(
        model=loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": batch,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}, "steps_per_print": 10**9})
    batches = [{"x": jnp.asarray(rng.normal(size=(batch, dim)), jnp.float32),
                "y": jnp.asarray(rng.integers(0, 10, batch), jnp.int32)}
               for _ in range(4)]
    dt, final = _time_steps(engine, batches, steps, warmup)
    return {"metric": "simple_zero0_examples_per_sec",
            "value": round(batch * steps / dt, 1), "unit": "examples/s",
            "vs_baseline": None, "final_loss": final,
            "device": jax.devices()[0].platform}


def rung2_gpt2_zero1():
    """Rung 2: GPT-2-small, ZeRO-1, FusedAdam."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import (TransformerLM, gpt2_config,
                                                  init_params, make_loss_fn)

    dev = jax.devices()[0]
    peak = peak_flops(dev)  # before any step: no peak on record, no MFU row
    cfg = gpt2_config("small", dtype=jnp.bfloat16)
    batch, seq, steps, warmup = 8, 1024, 20, 3
    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=seq)
    engine, *_ = ds.initialize(
        model=make_loss_fn(model), model_parameters=params,
        config={"train_micro_batch_size_per_gpu": batch,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1},
                "bf16": {"enabled": True},
                "steps_per_print": 10**9})
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)}
        for _ in range(4)]
    dt, final = _time_steps(engine, batches, steps, warmup)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.state.params))
    tok_s = batch * seq * steps / dt / len(jax.devices())
    # tied embeddings: the lm head matmul reuses the table, stays in 6N
    mfu = model_flops_per_token(cfg, seq, n_params) * tok_s / peak
    return {"metric": "gpt2s_zero1_fusedadam_tokens_per_sec_per_chip",
            "value": round(tok_s, 1), "unit": "tok/s/chip", "vs_baseline": None,
            "mfu": round(mfu, 4), "n_params": n_params, "final_loss": final,
            "device": getattr(dev, "device_kind", dev.platform)}


def rung4_pipeline_bubble():
    """Rung 4: pipeline 4 stages x dp=2 on the 8-device mesh — bubble check.

    A dp-vs-pp wall-clock comparison is meaningless on a virtual CPU mesh
    (8 'devices' share the same cores, so replica scheduling artifacts
    dominate). The honest single-box metric is pipeline-INTERNAL: the same
    global batch split into m=2 vs m=8 microbatches. With per-step time
    t(m) ~ W*(1 + (p-1)/m), the ideal ratio t(2)/t(8) is
    (1+(p-1)/2)/(1+(p-1)/8); how closely the measured ratio tracks it is the
    bubble accounting. (Reference rung: Megatron-GPT 1.3B pp=4; shapes scaled
    to the CPU mesh, so the RATIO is the metric, not tok/s.)"""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import Topology, TopologySpec, set_topology
    from deepspeed_tpu.runtime.pipe.pipeline import (make_pipeline_loss_fn,
                                                     pipeline_param_specs)

    H, V, B, S, L, m, p = 128, 256, 32, 32, 8, 8, 4
    rng = np.random.default_rng(0)
    params = {
        "embed": {"table": jnp.asarray(rng.normal(0, 0.02, (V, H)), jnp.float32)},
        "blocks": {"w": jnp.asarray(rng.normal(0, 0.05, (L, H, H)), jnp.float32),
                   "b": jnp.zeros((L, H), jnp.float32)},
        "head": {"w": jnp.asarray(rng.normal(0, 0.02, (H, V)), jnp.float32)},
    }

    def embed_fn(pp_, mb):
        return pp_["table"][mb["tokens"]]

    def block_fn(pp_, x):
        return x + jnp.tanh(x @ pp_["w"] + pp_["b"])

    def head_loss_fn(pp_, x, mb):
        logits = x @ pp_["w"]
        t = mb["tokens"][:, 1:]
        logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        tgt = jnp.take_along_axis(logits[:, :-1], t[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - tgt)

    batches = [{"tokens": jnp.asarray(
        rng.integers(0, V, (B, S)), jnp.int32)} for _ in range(4)]
    steps, warmup = 12, 3

    def bench_pp(m_, v_=1):
        from deepspeed_tpu.runtime.pipe.pipeline import interleave_pipeline_params

        topo = Topology(TopologySpec(pp=p))
        set_topology(topo)
        pp_params = (interleave_pipeline_params(params, p, v_) if v_ > 1
                     else params)
        loss_fn = make_pipeline_loss_fn(embed_fn, block_fn, head_loss_fn,
                                        num_layers=L, num_stages=p,
                                        num_microbatches=m_, virtual_stages=v_)
        engine, *_ = ds.initialize(
            model=loss_fn, model_parameters=pp_params,
            config={"train_micro_batch_size_per_gpu": B,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "pipeline": {"stages": p}, "steps_per_print": 10**9},
            topology=topo, param_specs=pipeline_param_specs(pp_params))
        return _time_steps(engine, batches, steps, warmup)

    t_m2, _ = bench_pp(2)
    t_m8, _ = bench_pp(m)
    t_int, _ = bench_pp(m, v_=2)  # interleaved: bubble (p-1)/(v*m)
    set_topology(Topology(TopologySpec()))
    ideal_ratio = (1 + (p - 1) / 2) / (1 + (p - 1) / m)
    measured = t_m2 / t_m8
    return {"metric": "pipeline_pp4_bubble_ratio_m2_over_m8",
            "value": round(measured, 4), "unit": "ratio",
            "vs_baseline": round(measured / ideal_ratio, 4),
            "ideal_ratio": round(ideal_ratio, 4),
            "t_m2_s": round(t_m2, 3), "t_m8_s": round(t_m8, 3),
            "t_interleaved_v2_s": round(t_int, 3),
            "interleaved_speedup_vs_gpipe": round(t_m8 / t_int, 4),
            "microbatches": m, "stages": p, "device": "cpu-mesh-8"}


def rung5_moe_ulysses():
    """Rung 5: Mixtral-style MoE (ep=4) + Ulysses (sp=2) — capacity-gating
    vs dropless grouped-GEMM step time on the 8-device mesh."""
    import dataclasses

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  make_loss_fn, mixtral_config)
    from deepspeed_tpu.parallel import Topology, TopologySpec, set_topology

    base = mixtral_config("tiny", num_layers=2, hidden_size=128,
                          intermediate_size=256, num_heads=8, num_kv_heads=4,
                          vocab_size=512, max_seq_len=64, num_experts=4,
                          sequence_parallel=True, dtype=jnp.float32)
    batch, seq, steps, warmup = 16, 64, 10, 3
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, base.vocab_size, (batch, seq)), jnp.int32)}
        for _ in range(4)]

    def bench_one(cfg):
        topo = Topology(TopologySpec(sp=2, ep=4))
        set_topology(topo)
        model = TransformerLM(cfg)
        params = init_params(model, batch=1, seq=seq)
        engine, *_ = ds.initialize(
            model=make_loss_fn(model), model_parameters=params,
            config={"train_micro_batch_size_per_gpu": batch,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "sequence_parallel_size": 2,
                    "moe": {"enabled": True, "ep_size": 4, "num_experts": 4},
                    "zero_optimization": {"stage": 2},
                    "steps_per_print": 10**9},
            topology=topo)
        return _time_steps(engine, batches, steps, warmup)

    t_cap, loss_cap = bench_one(dataclasses.replace(base, moe_dropless=False))
    t_drop, loss_drop = bench_one(dataclasses.replace(base, moe_dropless=True))
    set_topology(Topology(TopologySpec()))
    return {"metric": "moe_ep4_sp2_dropless_vs_capacity_ratio",
            "value": round(t_cap / t_drop, 4), "unit": "ratio",
            "vs_baseline": None,
            "t_capacity_s": round(t_cap, 3), "t_dropless_s": round(t_drop, 3),
            "final_loss_capacity": loss_cap, "final_loss_dropless": loss_drop,
            "device": "cpu-mesh-8"}


def rung3b_big_model():
    """Rung 3b: the ≥1B-param single-chip row — largest
    Llama-shaped config that trains on ONE chip with bf16 + remat +
    ZeRO-Offload (host SIMD Adam, ``csrc/adam/cpu_adam.cpp``); fp32 master +
    moments live on host, so HBM holds only bf16 params + fp32 grad
    accumulator + remat activations. ``docs/scaling_7b.md`` extrapolates
    from this measurement to Llama-2-7B on a v5e pod slice.

    Knobs (all optional): BIG_LAYERS/BIG_HIDDEN/BIG_INTER, BIG_BATCH,
    BIG_GAS, BIG_GRAD_DTYPE (device->host transport: float32|bfloat16)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config, make_loss_fn)

    dev = jax.devices()[0]
    peak = peak_flops(dev)  # before any step: no peak on record, no MFU row
    env = os.environ.get
    # the "1b" preset is the TinyLlama-1.1B shape (h=2048, L=22, GQA
    # 32/4, inter=5632) — 1.12B params with the untied head
    over = {k[4:].lower(): int(v) for k, v in os.environ.items()
            if k in ("BIG_LAYERS", "BIG_HIDDEN", "BIG_INTER")}
    over = {{"layers": "num_layers", "hidden": "hidden_size",
             "inter": "intermediate_size"}[k]: v for k, v in over.items()}
    cfg = llama_config("1b", max_seq_len=2048, dtype=jnp.bfloat16,
                       remat=True, **over)
    batch, seq = int(env("BIG_BATCH", "4")), 2048
    gas = int(env("BIG_GAS", "8"))
    steps, warmup = 3, 2

    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=seq)
    config = {"train_micro_batch_size_per_gpu": batch,
              "gradient_accumulation_steps": gas,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
              "zero_optimization": {"stage": 3,
                                    "offload_optimizer": {"device": "cpu"}},
              "bf16": {"enabled": True},
              "gradient_clipping": 1.0, "steps_per_print": 10**9}
    gd = env("BIG_GRAD_DTYPE")
    if gd:
        config["zero_optimization"]["offload_optimizer"]["grad_dtype"] = gd
    engine, *_ = ds.initialize(model=make_loss_fn(model),
                               model_parameters=params, config=config)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(engine.state.params))
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (gas * batch, seq)), jnp.int32)}
        for _ in range(4)]
    dt, final = _time_steps(engine, batches, steps, warmup)
    tok_s = gas * batch * seq * steps / dt / len(jax.devices())
    n_matmul = n_params - cfg.vocab_size * cfg.hidden_size
    mfu = model_flops_per_token(cfg, seq, n_matmul) * tok_s / peak

    # host-link bandwidth (the ZeRO-Offload tax): measured directly so the
    # memo can separate compute MFU from transport. 256 MiB probe.
    probe = jnp.ones((64 * 2**20,), jnp.float32)
    jax.block_until_ready(probe)
    t0 = time.perf_counter(); h = jax.device_get(probe)
    d2h = time.perf_counter() - t0
    t0 = time.perf_counter(); jax.block_until_ready(jax.device_put(h))
    h2d = time.perf_counter() - t0
    nb = probe.size * 4

    return {"metric": "llama_1b_offload_bf16_remat_mfu", "value": round(mfu, 4),
            "unit": "MFU", "vs_baseline": round(mfu / TARGET_MFU, 4),
            "tokens_per_sec_per_chip": round(tok_s, 1), "n_params": n_params,
            "batch": batch, "gas": gas, "grad_dtype": gd or "float32",
            "final_loss": final, "d2h_gbps": round(nb / d2h / 1e9, 2),
            "h2d_gbps": round(nb / h2d / 1e9, 2),
            "step_grad_bytes_gb": round(
                (2 if gd in ("bfloat16", "bf16") else 4) * n_params / 1e9, 2),
            "step_param_bytes_gb": round(2 * n_params / 1e9, 2),
            "device": getattr(dev, "device_kind", dev.platform)}


def collective_matmul_bench():
    """Latency-hiding collective matmul (ops/collective_matmul.py): time the
    GSPMD gather-then-matmul / matmul-then-scatter composition against the
    ring-overlapped all_gather_matmul -> matmul_reduce_scatter pair on the
    available mesh (a Megatron-SP MLP-shaped round trip, fwd only). On a
    multi-chip TPU mesh the ratio is the latency actually hidden; on the
    virtual CPU mesh the line documents parity wiring (relative numbers
    only). Emits the `collective_matmul` line either way."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.ops.collective_matmul import (all_gather_matmul,
                                                     matmul_reduce_scatter)
    from deepspeed_tpu.utils.shard_map_compat import shard_map_nocheck

    devs = np.array(jax.devices())
    n = len(devs)
    if n < 2:
        return {"metric": "collective_matmul", "value": None, "unit": "ratio",
                "vs_baseline": None, "error": "needs a >=2 device mesh"}
    mesh = Mesh(devs, ("tp",))
    on_tpu = devs[0].platform == "tpu"
    if on_tpu:
        B, S, D, F, dtype = 4, 4096, 4096, 11008 - 11008 % n, jnp.bfloat16
        reps_lo, reps_hi = 4, 24
    else:
        B, S, D, F, dtype = 2, 256, 256, 1024, jnp.float32
        reps_lo, reps_hi = 2, 6
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, S, D)) * 0.1, dtype)
    wu = jnp.asarray(rng.normal(size=(D, F)) * 0.02, dtype)
    wd = jnp.asarray(rng.normal(size=(F, D)) * 0.02, dtype)

    def make(fused, reps):
        def unfused_body(x_, wu_, wd_):
            full = lax.all_gather(x_, "tp", axis=1, tiled=True)   # [B, S, D]
            h = jnp.einsum("...k,kn->...n", full, wu_)            # [B, S, F/n]
            out = jnp.einsum("...k,kn->...n", h, wd_)             # [B, S, D]
            return lax.psum_scatter(out, "tp", scatter_dimension=1, tiled=True)

        def fused_body(x_, wu_, wd_):
            h = all_gather_matmul(x_, wu_, "tp")
            return matmul_reduce_scatter(h, wd_, "tp")

        body = fused_body if fused else unfused_body

        def loop(x_, wu_, wd_):
            def step(c, _):
                return body(c, wu_, wd_) * dtype(1e-2) + c, ()
            c, _ = jax.lax.scan(step, x_, None, length=reps)
            return c[0, 0, 0]

        return jax.jit(shard_map_nocheck(
            loop, mesh,
            in_specs=(P(None, "tp", None), P(None, "tp"), P("tp", None)),
            out_specs=P()))

    def timed(fused):
        f_lo, f_hi = make(fused, reps_lo), make(fused, reps_hi)
        float(f_lo(x, wu, wd)); float(f_hi(x, wu, wd))  # compile + drain
        t0 = time.perf_counter(); float(f_lo(x, wu, wd))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); float(f_hi(x, wu, wd))
        t_hi = time.perf_counter() - t0
        return (t_hi - t_lo) / (reps_hi - reps_lo)

    t_unfused = timed(fused=False)
    t_fused = timed(fused=True)
    return {"metric": "collective_matmul",
            "value": round(t_unfused / t_fused, 4), "unit": "ratio",
            "vs_baseline": None,
            "t_fused_s": round(t_fused, 6), "t_unfused_s": round(t_unfused, 6),
            "shape": {"B": B, "S": S, "D": D, "F": F},
            "devices": n,
            "device": "tpu" if on_tpu else f"cpu-mesh-{n}"}


def quantized_collectives_bench():
    """Rung qx (compressed collectives, comm/compressed.py): time the exact
    fp32 mean all-reduce against the EQuARX-style two-stage int8
    quantized_all_reduce on a gradient-sized vector, and report the comms
    ledger's logical-vs-wire bytes (the ≥3.5x on-wire reduction). On a
    multi-chip TPU mesh the time ratio is real bandwidth recovered; on the
    virtual CPU mesh the ledger numbers are the metric (both meshes run the
    same program)."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.comm.compressed import quantized_all_reduce
    from deepspeed_tpu.utils.shard_map_compat import shard_map_nocheck

    devs = np.array(jax.devices())
    n = len(devs)
    if n < 2:
        return {"metric": "quantized_allreduce", "value": None, "unit": "ratio",
                "vs_baseline": None, "error": "needs a >=2 device mesh"}
    mesh = Mesh(devs, ("dp",))
    on_tpu = devs[0].platform == "tpu"
    count = (32 * 2**20) if on_tpu else 2**22  # fp32 elements ("DP grads")
    reps_lo, reps_hi = (4, 24) if on_tpu else (2, 6)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(count,)) * 0.1, jnp.float32)

    def make(quant, reps):
        def loop(v):
            def body(c, _):
                r = (quantized_all_reduce(c, "dp") if quant
                     else lax.pmean(c, "dp"))
                return r * jnp.float32(0.999) + c * jnp.float32(1e-3), ()
            c, _ = lax.scan(body, v, None, length=reps)
            return c[0]

        return jax.jit(shard_map_nocheck(loop, mesh, in_specs=P(),
                                         out_specs=P()))

    def timed(quant):
        f_lo, f_hi = make(quant, reps_lo), make(quant, reps_hi)
        float(f_lo(x)); float(f_hi(x))  # compile + drain
        t0 = time.perf_counter(); float(f_lo(x))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter(); float(f_hi(x))
        t_hi = time.perf_counter() - t0
        return (t_hi - t_lo) / (reps_hi - reps_lo)

    # ledger: probe exactly ONE traced quantized reduction -> logical vs
    # on-wire bytes, then drop the probe entry so the _with_ledger snapshot
    # attached to this row doesn't mix it with the timed compiles below.
    # Restore enablement as found (the --ladder harness already has it on).
    logger = dist.get_comms_logger()
    was_enabled = logger.enabled
    logger.configure(enabled=True, prof_all=True)
    logger.reset()
    jax.eval_shape(make(True, 1), x)
    row = logger.totals().get("quantized_all_reduce", {})
    logger.reset()
    if not was_enabled:
        logger.configure(enabled=False)
    wire_reduction = (row["bytes"] / row["wire_bytes"]
                      if row.get("wire_bytes") else None)

    t_exact = timed(quant=False)
    t_quant = timed(quant=True)
    return {"metric": "quantized_allreduce",
            "value": round(t_exact / t_quant, 4), "unit": "ratio",
            "vs_baseline": None,
            "t_exact_s": round(t_exact, 6), "t_quantized_s": round(t_quant, 6),
            "elements": count, "devices": n,
            "logical_bytes": row.get("bytes"), "wire_bytes": row.get("wire_bytes"),
            "wire_reduction": round(wire_reduction, 2) if wire_reduction else None,
            "device": "tpu" if on_tpu else f"cpu-mesh-{n}"}


def planner_bench():
    """Rung plan (comm/planner): resolve the five wired collective sites on
    this mesh with the planner in static mode, then time each resolved
    implementation against the XLA-native default through the SAME
    microbenchmark harness ``measure`` mode uses — the planned-vs-default
    line. On a multi-chip TPU mesh the ratios are real; on the virtual CPU
    mesh the decisions + plan table are the artifact (ratios are relative
    wiring numbers only)."""
    import tempfile

    from deepspeed_tpu.comm.planner import (benchmark_site, configure_planner,
                                            make_site)
    from deepspeed_tpu.parallel.topology import (Topology, TopologySpec,
                                                 get_topology, set_topology)

    devs = np.array(jax.devices())
    n = len(devs)
    if n < 4:
        return {"metric": "comm_planner", "value": None, "unit": "ratio",
                "vs_baseline": None, "error": "needs a >=4 device mesh"}
    # a mesh exercising every wired axis: sp/tp/ep all real when 8+ devices
    spec = (TopologySpec(ep=2, sp=2, tp=2) if n % 8 == 0
            else TopologySpec(ep=2))
    set_topology(Topology(spec))
    topo = get_topology()
    on_tpu = devs[0].platform == "tpu"
    grad_n = (32 * 2**20) if on_tpu else 2**20
    planner = configure_planner("static",
                                cache_dir=tempfile.mkdtemp(prefix="dstpu_plan_"))
    sites = [
        make_site(op="all_reduce", shape=(grad_n,), dtype="float32",
                  axes=topo.dp_axes, consumer="dp-grad"),
        make_site(op="all_to_all", shape=(4, 256, 8, 64), dtype="float32",
                  axes=("sp",), consumer="ulysses"),
        make_site(op="all_to_all", shape=(8, 4, 64, 128), dtype="float32",
                  axes=("ep",), consumer="moe-a2a"),
        make_site(op="all_gather", shape=(grad_n // 8,), dtype="float32",
                  axes=("dp_outer", "ep"), consumer="zeropp"),
        make_site(op="reduce_scatter", shape=(grad_n // 4,), dtype="float32",
                  axes=("dp_outer", "ep"), consumer="zeropp"),
        make_site(op="gather_matmul", shape=(4, 512, 256), dtype="float32",
                  axes=("tp",), consumer="tp-linear"),
    ]
    max_elems = (1 << 22) if on_tpu else (1 << 16)
    rows, ratios = [], []
    for site in sites:
        d = planner.resolve(site)
        row = {"site": site.signature(), "impl": d.impl, "source": d.source,
               "est_us": d.est_us}
        try:
            t_def = benchmark_site(site, "xla", max_elems=max_elems)
            t_plan = (t_def if d.impl == "xla"
                      else benchmark_site(site, d.impl, block=d.block,
                                          max_elems=max_elems))
            row.update(t_default_s=round(t_def, 6),
                       t_planned_s=round(t_plan, 6),
                       ratio=round(t_def / t_plan, 4) if t_plan else None)
            if row["ratio"]:
                ratios.append(row["ratio"])
        except Exception as e:  # keep the rung row even if one probe fails
            row["error"] = str(e)[:160]
        rows.append(row)
    value = round(float(np.prod(ratios)) ** (1 / len(ratios)), 4) if ratios else None
    return {"metric": "comm_planner", "value": value, "unit": "ratio",
            "vs_baseline": None, "devices": n,
            "mesh": {k: int(v) for k, v in topo.mesh.shape.items()},
            "plan": rows, "device": "tpu" if on_tpu else f"cpu-mesh-{n}"}


def resilience_bench():
    """Rung rz (resilience subsystem, runtime/resilience/): snapshot and
    restore latency for a training-state-sized pytree. The number that
    matters for the step loop is the ASYNC call-return latency (device→host
    fetch only — the disk write overlaps training on the writer thread);
    the sync write gives the disk-bound MB/s floor and the ratio between
    them is the stall the background writer removes from every cadence
    snapshot."""
    import shutil as _shutil
    import tempfile

    from deepspeed_tpu.runtime.resilience import SnapshotManager

    on_tpu = jax.devices()[0].platform == "tpu"
    mb = 256 if on_tpu else 64
    n = (mb << 20) // 4
    rng = np.random.default_rng(0)
    # a realistic state mix: params + two adam moments + a few scalars
    third = n // 3
    tree = {"params": jnp.asarray(rng.normal(size=(third,)), jnp.float32),
            "exp_avg": jnp.asarray(rng.normal(size=(third,)), jnp.float32),
            "exp_avg_sq": jnp.asarray(rng.normal(size=(third,)), jnp.float32),
            "step": jnp.asarray(3, jnp.int32)}
    jax.block_until_ready(tree)
    total_mb = sum(x.nbytes for x in jax.tree.leaves(tree)) / 2**20

    d = tempfile.mkdtemp(prefix="dstpu_rz_")
    try:
        sm = SnapshotManager(d, keep=4, use_async=False)
        sm.snapshot(tree, step=0)  # warm the path (dir creation, imports)
        t0 = time.perf_counter()
        sm.snapshot(tree, step=1)
        sync_s = time.perf_counter() - t0

        sma = SnapshotManager(d, keep=4, use_async=True)
        t0 = time.perf_counter()
        sma.snapshot(tree, step=2)
        async_call_s = time.perf_counter() - t0  # the step-path stall
        t0 = time.perf_counter()
        sma.wait()
        drain_s = time.perf_counter() - t0
        sma.close()

        t0 = time.perf_counter()
        sm.restore_tree(tree)
        restore_s = time.perf_counter() - t0
    finally:
        _shutil.rmtree(d, ignore_errors=True)

    return {"metric": "resilience_snapshot_overlap",
            "value": round(sync_s / async_call_s, 2), "unit": "x",
            "vs_baseline": None, "state_mb": round(total_mb, 1),
            "sync_ms": round(sync_s * 1e3, 2),
            "sync_mb_per_s": round(total_mb / sync_s, 1),
            "async_call_ms": round(async_call_s * 1e3, 2),
            "async_drain_ms": round(drain_s * 1e3, 2),
            "restore_ms": round(restore_s * 1e3, 2),
            "restore_mb_per_s": round(total_mb / restore_s, 1),
            "device": "tpu" if on_tpu else "cpu"}


def watchdog_bench():
    """Rung wd (fleet watchdog, runtime/resilience/watchdog.py +
    heartbeat.py): per-step arm/disarm overhead — the only fleet-tier cost
    that rides the hot step path, so the target is noise level (single-digit
    microseconds: one lock acquire and a deque append) — plus heartbeat
    beacon write/read latency, which is off the step path but bounds the
    usable beacon cadence on a shared filesystem."""
    import shutil as _shutil
    import tempfile

    from deepspeed_tpu.runtime.resilience import (FileHeartbeatTransport,
                                                  HealthTable,
                                                  HeartbeatWriter,
                                                  StepWatchdog)

    d = tempfile.mkdtemp(prefix="dstpu_wd_")
    try:
        wd = StepWatchdog(d, floor_s=120.0, cap_s=600.0)
        for i in range(100):  # warm the lock/deque path
            wd.arm(i)
            wd.disarm()
        n = 5000
        t0 = time.perf_counter()
        for i in range(n):
            wd.arm(i)
            wd.disarm()
        arm_disarm_us = (time.perf_counter() - t0) / n * 1e6
        assert not wd.fired, "watchdog fired during the overhead bench"
        wd.stop()

        transport = FileHeartbeatTransport(d)
        writer = HeartbeatWriter(transport, rank=0)
        table = HealthTable(transport)
        for r in range(1, 4):  # a small fleet so read parses several beacons
            HeartbeatWriter(transport, rank=r).beat(step=10, step_time_s=0.1)
        m = 200
        t0 = time.perf_counter()
        for i in range(m):
            writer.beat(step=i, step_time_s=0.1)
        hb_write_ms = (time.perf_counter() - t0) / m * 1e3
        t0 = time.perf_counter()
        for _ in range(m):
            table.read()
        hb_read_ms = (time.perf_counter() - t0) / m * 1e3
    finally:
        _shutil.rmtree(d, ignore_errors=True)

    return {"metric": "watchdog_arm_disarm_us",
            "value": round(arm_disarm_us, 2), "unit": "us/step",
            "vs_baseline": None,
            "heartbeat_write_ms": round(hb_write_ms, 3),
            "heartbeat_read_ms": round(hb_read_ms, 3),
            "fleet_beacons_read": 4,
            "device": jax.devices()[0].platform}


def fused_hotpath_bench():
    """Rung fl (fused training hot path, ISSUE 6): time the XLA loss
    epilogue — full-vocab fp32 logits materialized, then CE — against the
    Pallas fused LM loss (ops/pallas/fused_loss.py), and the XLA attention
    against the flash kernel, both fwd+bwd (the training direction). On a
    real TPU the ratios are HBM traffic actually removed from the step; on
    CPU the kernels run in interpret mode, so the row documents wiring
    parity and the ledger, not speed."""
    from deepspeed_tpu.models.transformer import attention_core
    from deepspeed_tpu.sequence.cross_entropy import sharded_lm_loss

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    rng = np.random.default_rng(0)
    if on_tpu:
        # the headline bench's loss shape: batch 6 x seq 2048 x vocab 32000
        B, S, E, V = 6, 2048, 1536, 32000
        AB, AS, AH, AHK, AD = 6, 2048, 12, 12, 128
        dtype, repeats = jnp.bfloat16, 3
    else:
        B, S, E, V = 2, 64, 32, 256
        AB, AS, AH, AHK, AD = 1, 256, 4, 2, 32
        dtype, repeats = jnp.float32, 1

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))  # compile
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    # -- loss: fwd+bwd wrt hidden and head kernel --------------------------
    hidden = jnp.asarray(rng.normal(size=(B, S, E)) * 0.1, dtype)
    kernel = jnp.asarray(rng.normal(size=(E, V)) * 0.02, dtype)
    tokens = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)

    def loss_fn(impl):
        def f(h, k):
            return sharded_lm_loss(h, k, tokens, loss_impl=impl)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    t_loss_xla = timed(loss_fn("xla"), hidden, kernel)
    t_loss_fused = timed(loss_fn("fused"), hidden, kernel)

    # -- attention: fwd+bwd, GQA + explicit sm_scale -----------------------
    q = jnp.asarray(rng.normal(size=(AB, AS, AH, AD)) * 0.1, dtype)
    k = jnp.asarray(rng.normal(size=(AB, AS, AHK, AD)) * 0.1, dtype)
    v = jnp.asarray(rng.normal(size=(AB, AS, AHK, AD)) * 0.1, dtype)

    def attn_fn(impl):
        def f(q_, k_, v_):
            out = attention_core(q_, k_, v_, causal=True, impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    t_attn_xla = timed(attn_fn("xla"), q, k, v)
    t_attn_flash = timed(attn_fn("flash"), q, k, v)

    logits_mb = B * S * V * 4 / 2**20  # the tensor the fused loss deletes
    return {"metric": "fused_hotpath_loss_speedup",
            "value": round(t_loss_xla / t_loss_fused, 4), "unit": "ratio",
            "vs_baseline": None,
            "attn_flash_speedup": round(t_attn_xla / t_attn_flash, 4),
            "t_loss_xla_s": round(t_loss_xla, 6),
            "t_loss_fused_s": round(t_loss_fused, 6),
            "t_attn_xla_s": round(t_attn_xla, 6),
            "t_attn_flash_s": round(t_attn_flash, 6),
            "loss_shape": {"B": B, "S": S, "E": E, "V": V},
            "attn_shape": {"B": AB, "S": AS, "H": AH, "Hk": AHK, "D": AD},
            "logits_mb_removed": round(logits_mb, 1),
            "device": getattr(dev, "device_kind", dev.platform)}


def serving_bench():
    """Rung sv (serving tier, deepspeed_tpu/serving/): seeded OPEN-LOOP
    Poisson traffic against an LLMServer over the v2 ragged engine —
    arrivals follow the fixed schedule regardless of completions, so the
    recorded TTFT/e2e percentiles include real queueing, not a closed
    loop's self-throttled flattery. Reports tokens/s-per-chip as the value
    plus p50/p99 TTFT and e2e latency; on CPU a tiny model documents the
    serving-path wiring and relative latencies, on a TPU the decode-bench
    model shape makes the row a real serving number."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config)
    from deepspeed_tpu.serving import (LengthDist, LLMServer, OpenLoopTraffic,
                                       TrafficConfig)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = llama_config("7b", num_layers=12, hidden_size=1536,
                           intermediate_size=4096, num_heads=12, num_kv_heads=4,
                           vocab_size=32000, max_seq_len=4096,
                           dtype=jnp.bfloat16)
        eng_cfg = RaggedInferenceEngineConfig(
            token_budget=512, max_ragged_sequence_count=16, max_chunk_size=256,
            num_kv_blocks=400, kv_block_size=128, max_blocks_per_seq=8,
            dtype="bfloat16")
        traffic = TrafficConfig(rate_rps=8.0, num_requests=64, seed=7,
                                vocab_size=cfg.vocab_size,
                                prompt_len=LengthDist("uniform", 64, 256),
                                output_len=LengthDist("uniform", 32, 96),
                                deadline_s=60.0)
    else:
        cfg = llama_config("7b", num_layers=2, hidden_size=128,
                           intermediate_size=256, num_heads=4, num_kv_heads=2,
                           vocab_size=1024, max_seq_len=256, dtype=jnp.float32)
        eng_cfg = RaggedInferenceEngineConfig(
            token_budget=64, max_ragged_sequence_count=8, max_chunk_size=16,
            num_kv_blocks=96, kv_block_size=8, max_blocks_per_seq=8,
            dtype="float32")
        traffic = TrafficConfig(rate_rps=40.0, num_requests=32, seed=7,
                                vocab_size=cfg.vocab_size,
                                prompt_len=LengthDist("uniform", 8, 24),
                                output_len=LengthDist("uniform", 8, 16),
                                deadline_s=30.0)

    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=64)
    engine = InferenceEngineV2(model, params, eng_cfg)
    # warm the compile caches OFF the clock (the packed-step program AND the
    # fused-decode programs at the table widths generation grows through),
    # then serve the seeded schedule
    engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=4)
    fused_chunk = 8
    warm_new = min(6 * fused_chunk,
                   eng_cfg.max_blocks_per_seq * eng_cfg.kv_block_size - 16)
    engine.put([10**9], [np.arange(1, 9, dtype=np.int32)],
               max_new_tokens=warm_new)
    while any(s.in_prefill for s in engine.state_manager.all()):
        engine.step()
    for _ in range(4):
        engine.decode_batch(fused_chunk)
    engine.flush(10**9)
    server = LLMServer(engine, policy="deadline", max_queue=512,
                       fused_decode_chunk=fused_chunk).start()
    t0 = time.perf_counter()
    resps, rejected = OpenLoopTraffic(traffic).run(
        lambda req: server.submit(req))
    drained = server.drain(timeout=1800)
    wall = time.perf_counter() - t0
    m = server.metrics
    snap = m.snapshot()
    n_chips = len(jax.devices())
    tps_chip = m.tokens_out / wall / n_chips
    return {"metric": "serving_open_loop_tokens_per_sec_per_chip",
            "value": round(tps_chip, 1), "unit": "tok/s/chip",
            "vs_baseline": None,
            "ttft_p50_ms": snap["ttft"]["p50_ms"],
            "ttft_p99_ms": snap["ttft"]["p99_ms"],
            "e2e_p50_ms": snap["e2e"]["p50_ms"],
            "e2e_p99_ms": snap["e2e"]["p99_ms"],
            "queue_wait_p50_ms": snap["queue_wait"]["p50_ms"],
            "completed": snap["completed"], "rejected": len(rejected),
            "preemptions": snap["preemptions"],
            "sla_violations": snap["sla_violations"],
            "tokens_out": snap["tokens_out"],
            "rate_rps": traffic.rate_rps, "num_requests": traffic.num_requests,
            "drained": drained, "wall_s": round(wall, 3),
            "policy": "deadline", "seed": traffic.seed,
            # which attention paths served this row (engine_v2 resolution,
            # stamped into ServingMetrics) + the fused-decode chunk width
            "attn_impl": snap["attn_impl"],
            "decode_attn_impl": snap["decode_attn_impl"],
            "fused_decode_chunk": server.fused_decode_chunk,
            "device": getattr(dev, "device_kind", dev.platform)}


def serving_prefix_reuse_bench():
    """Rung sv2 (prefix KV reuse + speculative decoding, ISSUE 16): the
    SAME seeded prefix-heavy open-loop trace (Zipf-reused system prompts +
    unique suffixes) served twice — a baseline arm with the prefix cache
    and spec decode off, and a reuse arm with ``enable_prefix_cache=True``
    + n-gram spec decode — and the value is the tokens/s-per-chip speedup
    of the reuse arm over the baseline. Both arms must produce BITWISE
    identical greedy tokens per request_id (the tentpole's correctness
    invariant: content-addressed reuse and draft-verify change only the
    schedule, never the math), and the rung asserts it before reporting.
    A third pass re-serves the trace with the reuse arm under a seeded
    chaos schedule (kv_exhaustion at admission, slow_prefill + drop_token
    on the replica) and must complete every request with the same bitwise
    output — zero lost requests, per the PR 15 soak convention."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config)
    from deepspeed_tpu.runtime.resilience import (ChaosEvent, ChaosSchedule,
                                                  configure_chaos)
    from deepspeed_tpu.serving import (LengthDist, LLMServer, OpenLoopTraffic,
                                       TrafficConfig)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = llama_config("7b", num_layers=12, hidden_size=1536,
                           intermediate_size=4096, num_heads=12,
                           num_kv_heads=4, vocab_size=32000, max_seq_len=4096,
                           dtype=jnp.bfloat16)
        eng_over = dict(token_budget=512, max_ragged_sequence_count=16,
                        max_chunk_size=256, num_kv_blocks=640,
                        kv_block_size=128, max_blocks_per_seq=16,
                        dtype="bfloat16")
        traffic = TrafficConfig(rate_rps=64.0, num_requests=48, seed=11,
                                vocab_size=cfg.vocab_size,
                                prompt_len=LengthDist("uniform", 16, 48),
                                output_len=LengthDist("uniform", 16, 32),
                                system_prompt_pool=4, system_prompt_len=1024)
    else:
        cfg = llama_config("7b", num_layers=2, hidden_size=128,
                           intermediate_size=256, num_heads=4, num_kv_heads=2,
                           vocab_size=1024, max_seq_len=512,
                           dtype=jnp.float32)
        eng_over = dict(token_budget=64, max_ragged_sequence_count=8,
                        max_chunk_size=16, num_kv_blocks=512, kv_block_size=8,
                        max_blocks_per_seq=48, dtype="float32")
        # saturating rate: every request queues immediately, so the wall
        # clock measures service time (prefill work the cache deletes),
        # not open-loop idle gaps
        traffic = TrafficConfig(rate_rps=500.0, num_requests=24, seed=11,
                                vocab_size=cfg.vocab_size,
                                prompt_len=LengthDist("uniform", 4, 12),
                                output_len=LengthDist("uniform", 4, 8),
                                system_prompt_pool=3, system_prompt_len=320)

    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=64)
    fused_chunk = 8
    n_chips = len(jax.devices())

    def run_arm(reuse_on: bool):
        eng_cfg = RaggedInferenceEngineConfig(
            **eng_over, enable_prefix_cache=reuse_on,
            spec_decode_k=4 if reuse_on else 0)
        engine = InferenceEngineV2(model, params, eng_cfg)
        # warm the compile caches OFF the clock: packed step, fused-decode
        # chunk, and (reuse arm) the spec verify widths a repetitive prompt
        # actually drafts through — compiles must not bias either arm
        warm = np.tile(np.arange(1, 9, dtype=np.int32), 3)
        engine.generate([warm[:8]], max_new_tokens=4)
        engine.put([10**9], [warm], max_new_tokens=24)
        while any(s.in_prefill for s in engine.state_manager.all()):
            engine.step()
        for _ in range(6):
            if reuse_on:
                engine.spec_decode_batch()
            else:
                engine.decode_batch(fused_chunk)
        engine.flush(10**9)
        server = LLMServer(engine, policy="fcfs", max_queue=512,
                           fused_decode_chunk=fused_chunk).start()
        t0 = time.perf_counter()
        resps, rejected = OpenLoopTraffic(traffic).run(
            lambda req: server.submit(req))
        drained = server.drain(timeout=1800)
        wall = time.perf_counter() - t0
        snap = server.metrics.snapshot()
        outs = {r.request.request_id: np.asarray(r.result(timeout=5))
                for r in resps}
        assert not rejected and drained, \
            f"sv2 arm reuse={reuse_on}: rejected={len(rejected)} " \
            f"drained={drained}"
        tps = server.metrics.tokens_out / wall / n_chips
        return tps, snap, outs, wall

    tps_off, snap_off, outs_off, wall_off = run_arm(False)
    tps_on, snap_on, outs_on, wall_on = run_arm(True)
    # the tentpole invariant: reuse + draft-verify are schedule-only
    for rid, toks in outs_off.items():
        assert np.array_equal(toks, outs_on[rid]), \
            f"sv2: greedy divergence on {rid}"

    # chaos-soaked pass (PR 15 convention): same trace, reuse arm, seeded
    # serving faults — every request must still complete bitwise identical
    import random as _random
    rng = _random.Random(17)
    configure_chaos(None)
    try:
        configure_chaos(ChaosSchedule([
            ChaosEvent("kv_exhaustion", "scheduler.admit",
                       at=rng.randrange(2, 5), count=3),
            ChaosEvent("slow_prefill", "replica0",
                       at=rng.randrange(1, 4), param=0.01),
            ChaosEvent("drop_token", "replica0",
                       at=rng.randrange(8, 14), count=2),
        ], seed=17))
        _, snap_cz, outs_cz, _ = run_arm(True)
        lost = [rid for rid in outs_off if rid not in outs_cz
                or not np.array_equal(outs_off[rid], outs_cz[rid])]
        assert not lost, f"sv2 chaos pass lost/diverged: {lost}"
    finally:
        configure_chaos(None)

    return {"metric": "serving_prefix_reuse_speedup",
            "value": round(tps_on / tps_off, 3), "unit": "x",
            "vs_baseline": None,
            "tokens_per_sec_per_chip_reuse": round(tps_on, 1),
            "tokens_per_sec_per_chip_baseline": round(tps_off, 1),
            "ttft_p99_ms_reuse": snap_on["ttft"]["p99_ms"],
            "ttft_p99_ms_baseline": snap_off["ttft"]["p99_ms"],
            "e2e_p99_ms_reuse": snap_on["e2e"]["p99_ms"],
            "e2e_p99_ms_baseline": snap_off["e2e"]["p99_ms"],
            "prefix_hit_rate": snap_on["prefix_hit_rate"],
            "prefix_tokens_reused": snap_on["prefix_tokens_reused"],
            "prefix_blocks_shared": snap_on["prefix_blocks_shared"],
            "cow_forks": snap_on["cow_forks"],
            "spec_acceptance_rate": snap_on["spec_acceptance_rate"],
            "spec_steps": snap_on["spec_steps"],
            "greedy_parity": True,
            "chaos_completed": snap_cz["completed"],
            "chaos_lost": 0,
            "wall_s_reuse": round(wall_on, 3),
            "wall_s_baseline": round(wall_off, 3),
            "num_requests": traffic.num_requests, "seed": traffic.seed,
            "system_prompt_pool": traffic.system_prompt_pool,
            "system_prompt_len": traffic.system_prompt_len,
            "device": getattr(dev, "device_kind", dev.platform)}


def paged_decode_bench():
    """Rung pd (paged decode fastpath, ops/pallas/paged_attention.py
    paged_flash_decode): fused multi-token decode step time, the
    resident-pool pallas flash-decode kernel vs the gathered-page einsum
    reference, on fp KV pools and on int8 (values, scales) pools (dequant
    fused in-kernel vs dequant-on-gather), plus the per-step pool bytes
    each arm touches from the comms ledger (``paged_pool_gather`` = the
    einsum path's materialized copy, the tensor the kernel deletes;
    ``paged_pool_read`` = the kernel's in-place page-read upper bound).
    Value = per-token decode time of the impl the engine's auto resolution
    would actually serve on this host, so the lower-is-better gate tracks
    the serving decode hot path."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  llama_config)
    import deepspeed_tpu.comm as dist

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = llama_config("7b", num_layers=12, hidden_size=1536,
                           intermediate_size=4096, num_heads=12,
                           num_kv_heads=4, vocab_size=32000, max_seq_len=4096,
                           dtype=jnp.bfloat16)
        S, chunk, blocks, bs, bps = 16, 32, 400, 128, 8
        compute = "bfloat16"
    else:
        cfg = llama_config("7b", num_layers=2, hidden_size=128,
                           intermediate_size=256, num_heads=4, num_kv_heads=2,
                           vocab_size=512, max_seq_len=256, dtype=jnp.float32)
        S, chunk, blocks, bs, bps = 4, 8, 64, 8, 8
        compute = "float32"
    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(S)]
    max_new = bps * bs - 24              # fits max_blocks_per_seq worst-case
    logger = dist.get_comms_logger()
    # the pool-byte columns ARE the measurement: enable the ledger here so
    # a standalone `--rung pd` doesn't silently report zeros
    logger.configure(enabled=True, prof_all=True)
    pool_mb = None

    def run(backend, kv_dtype):
        nonlocal pool_mb
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            token_budget=S * 32, max_ragged_sequence_count=S,
            max_chunk_size=32, num_kv_blocks=blocks, kv_block_size=bs,
            max_blocks_per_seq=bps, dtype=compute, kv_cache_dtype=kv_dtype,
            decode_attn_backend=backend, decode_chunk=chunk))
        pool_mb = round(eng.kv.pool_nbytes() / 2**20, 2)
        eng.put(list(range(S)), prompts, max_new_tokens=max_new)
        while any(s.in_prefill for s in eng.state_manager.all()):
            eng.step()
        logger.reset()               # decode-trace pool rows only
        eng.decode_batch(chunk)      # compile + trace (ledger records here)
        tot = logger.totals()
        reps, best = 3, float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            got = eng.decode_batch(chunk)
            n = max((len(t) for t in got.values()), default=chunk)
            best = min(best, (time.perf_counter() - t0) / max(1, n))
        row = lambda op: tot.get(op, {}).get("bytes", 0)
        return (best * 1e3, row("paged_pool_gather"), row("paged_pool_read"),
                eng.decode_attn_impl)

    t_einsum, gather_b, _, _ = run("einsum", None)
    t_pallas, _, read_b, _ = run("pallas", None)
    t_einsum_q, gather_q, _, _ = run("einsum", "int8")
    t_pallas_q, _, read_q, _ = run("pallas", "int8")
    # the impl auto resolution serves on THIS host (heuristic: tpu->pallas)
    auto = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        num_kv_blocks=16, kv_block_size=bs, max_blocks_per_seq=2,
        dtype=compute)).decode_attn_impl
    served = t_pallas if auto == "pallas" else t_einsum
    return {"metric": "paged_decode_step_ms",
            "value": round(served, 4), "unit": "ms/tok",
            "vs_baseline": None, "served_impl": auto,
            "t_einsum_ms": round(t_einsum, 4),
            "t_pallas_ms": round(t_pallas, 4),
            "t_einsum_int8_ms": round(t_einsum_q, 4),
            "t_pallas_int8_ms": round(t_pallas_q, 4),
            "einsum_pool_gather_bytes_per_step": gather_b,
            "pallas_pool_read_bytes_per_step": read_b,
            "einsum_int8_pool_gather_bytes_per_step": gather_q,
            "pallas_int8_pool_read_bytes_per_step": read_q,
            "pool_mb": pool_mb, "decode_chunk": chunk, "seqs": S,
            "device": getattr(dev, "device_kind", dev.platform)}


def dcn_hierarchical_bench():
    """Rung ds (multi-slice DCN tier, comm/planner + comm/compressed.py):
    hierarchical-vs-flat DP-grad reduction on a 2-axis dp mesh — dp_outer=4
    declared the DCN axis via the planner's ``dcn_axes`` override, ep=2 as
    the slice-local ICI axis (simulated DCN split on the virtual CPU mesh;
    both arms run the same program a real multi-slice fleet would). Arms:
    flat int8 all-reduce over the whole dp span (every link, including the
    slow cross-slice one, carries the full quantized payload) vs the
    planner-synthesized multi-phase program (exact reduce-scatter over ICI,
    int8+error-feedback all-reduce over the DCN axis on the 1/ici-sized
    shard, all-gather back over ICI). Metric: DCN-class wire bytes per step
    from the comms ledger hop buckets — the bytes that actually cross the
    ~8x-slower link — with flat's full payload as the DCN-equivalent
    baseline; step times ride along (noise on CPU, as in rung qx: the
    ledger numbers are the measurement)."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.comm.planner import reset_planner
    from deepspeed_tpu.parallel import Topology, TopologySpec

    if len(jax.devices()) < 8:
        return {"metric": "dcn_hierarchical", "value": None, "unit": "ratio",
                "vs_baseline": None, "error": "needs an 8-device mesh"}

    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(size=(512, 1024)) * 0.05,
                                jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(1024, 64)) * 0.05,
                                jnp.float32)}  # ~0.59M params, ~2.4MB grads

    def loss_fn(p, batch, rng=None):
        x, y = batch
        pred = jnp.tanh(x @ p["w1"]) @ p["w2"]
        return jnp.mean((pred - y) ** 2)

    def batch(i, n=8 * 8):
        r = np.random.default_rng(1000 + i)
        x = jnp.asarray(r.normal(size=(n, 512)), jnp.float32)
        return (x, jnp.asarray(x[:, :64] * 0.5, jnp.float32))

    base = {"train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "steps_per_print": 10**9,
            # ledger on via the CONFIG: initialize() reconfigures the
            # fleet logger from it, so enabling by hand beforehand is wiped
            "comms_logger": {"enabled": True, "prof_all": True}}
    logger = dist.get_comms_logger()
    steps = 4

    def run(extra):
        cfg = dict(base)
        cfg.update(extra)
        logger.reset()
        eng, *_ = ds.initialize(model=loss_fn,
                                model_parameters=jax.tree.map(jnp.copy,
                                                              params),
                                config=cfg,
                                topology=Topology(TopologySpec(ep=2)))
        float(eng.train_batch(batch(0)))  # compile + first step
        totals, hops = logger.totals(), logger.hop_totals()
        logger.reset()
        t0 = time.perf_counter()
        losses = [float(eng.train_batch(batch(1 + i))) for i in range(steps)]
        dt = (time.perf_counter() - t0) / steps
        logger.reset()
        return eng, totals, hops, dt, losses

    # flat arm: int8 over the full dp span, no planner
    _, flat_tot, _, t_flat, _ = run({"compressed_collectives": "int8"})
    reset_planner()
    eng, prog_tot, prog_hops, t_prog, losses = run(
        {"comm_planner": {"mode": "static", "use_cache": False,
                          "dcn_axes": ["dp_outer"]}})
    from deepspeed_tpu.comm.planner import program_summary
    impl = eng._dp_grad_impl  # None when the planner picked the exact psum
    program = (program_summary(impl[2]) if impl and impl[0] == "program"
               else impl[0] if impl else "exact-xla")

    # per-trace normalization: each arm's collectives log once per trace of
    # the step function; the op counts say how many traces the arm saw
    flat_row = flat_tot.get("quantized_all_reduce", {})
    n_flat = max(flat_row.get("count", 1), 1)
    flat_wire = flat_row.get("wire_bytes", 0) // n_flat  # full span = DCN-class
    n_prog = max(prog_tot.get("program_reduce_scatter", {}).get("count", 1), 1)
    dcn_wire = prog_hops.get("dcn", 0) // n_prog
    ici_wire = prog_hops.get("ici", 0) // n_prog
    exact_bytes = 4 * sum(int(np.prod(p.shape)) for p in
                          jax.tree.leaves(params))  # what flat fp32 moves
    return {"metric": "dcn_hierarchical",
            "value": round(flat_wire / dcn_wire, 2) if dcn_wire else None,
            "unit": "dcn-wire-reduction",
            "vs_baseline": None, "program": program,
            "flat_int8_wire_bytes": flat_wire,
            "program_dcn_wire_bytes": dcn_wire,
            "program_ici_wire_bytes": ici_wire,
            "exact_flat_bytes": exact_bytes,
            "dcn_reduction_vs_exact": (round(exact_bytes / dcn_wire, 2)
                                       if dcn_wire else None),
            "t_flat_s": round(t_flat, 6), "t_program_s": round(t_prog, 6),
            "final_loss": round(losses[-1], 6),
            "devices": len(jax.devices()),
            "device": jax.devices()[0].platform}


def fused_phase_bench():
    """Rung t3 (fused compute-collective phase programs, comm/planner +
    ops/collective_matmul.py): fused vs sequenced dp-grad program on the
    simulated 2-axis DCN mesh (dp_outer=4 forced DCN, ep=2 slice-local —
    the ds rung's substrate). The fused arm is what comm_planner static now
    synthesizes organically: ``rs~fused_matmul(ep) > ar.int8_ef(dp_outer) >
    ag~fused_matmul(ep)`` — the ICI phases' ppermute hops ride between the
    producing/consuming matmul tiles instead of running as exposed
    transport. The sequenced arm replays the PR 8 program (same phase
    algebra, via=xla) through a hand-written plan-cache entry, so both
    arms move the SAME wire bytes and differ only in exposure. Metric: the
    fused program's exposed-collective fraction from the ledger hop
    exposure buckets (exposed wire bytes / total wire bytes per step) —
    the sequenced arm's fraction is 1.0 by construction, and the
    acceptance bar is strictly lower at equal wire bytes. A direct
    executor probe also proves fused-exact is BITWISE-identical to
    sequenced-exact (the ep=2 ring reduction is order-free)."""
    import dataclasses as _dc
    import shutil
    import tempfile

    import deepspeed_tpu as ds
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.comm.compressed import run_collective_program
    from deepspeed_tpu.comm.planner import (Plan, PlanCache, PlanDecision,
                                            get_planner, program_summary,
                                            reset_planner)
    from deepspeed_tpu.parallel import Topology, TopologySpec
    from deepspeed_tpu.utils.shard_map_compat import shard_map_nocheck
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 8:
        return {"metric": "fused_exposed_fraction", "value": None,
                "unit": "ratio", "vs_baseline": None,
                "error": "needs an 8-device mesh"}

    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(size=(512, 1024)) * 0.05,
                                jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(1024, 64)) * 0.05,
                                jnp.float32)}  # ~0.59M params, ~2.4MB grads

    def loss_fn(p, batch, rng=None):
        x, y = batch
        pred = jnp.tanh(x @ p["w1"]) @ p["w2"]
        return jnp.mean((pred - y) ** 2)

    def batch(i, n=8 * 8):
        r = np.random.default_rng(1000 + i)
        x = jnp.asarray(r.normal(size=(n, 512)), jnp.float32)
        return (x, jnp.asarray(x[:, :64] * 0.5, jnp.float32))

    base = {"train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "steps_per_print": 10**9,
            "comms_logger": {"enabled": True, "prof_all": True}}
    logger = dist.get_comms_logger()
    steps = 4

    def run(planner_cfg):
        cfg = dict(base)
        cfg["comm_planner"] = planner_cfg
        logger.reset()
        reset_planner()
        eng, *_ = ds.initialize(model=loss_fn,
                                model_parameters=jax.tree.map(jnp.copy,
                                                              params),
                                config=cfg,
                                topology=Topology(TopologySpec(ep=2)))
        losses = [float(eng.train_batch(batch(i))) for i in range(steps)]
        totals, expo = logger.totals(), logger.hop_exposure()
        logger.reset()
        return eng, totals, expo, losses

    def exposure_fraction(expo):
        wire = sum(v["wire"] for v in expo.values())
        exposed = sum(v["exposed"] for v in expo.values())
        return (exposed / wire if wire else None), wire

    # fused arm: what static synthesis picks on the DCN mesh today
    eng, f_tot, f_expo, losses = run({"mode": "static", "use_cache": False,
                                      "dcn_axes": ["dp_outer"]})
    impl = eng._dp_grad_impl
    if not impl or impl[0] != "program":
        return {"metric": "fused_exposed_fraction", "value": None,
                "unit": "ratio", "vs_baseline": None,
                "error": f"planner resolved {impl!r}, not a program"}
    fused_prog = impl[2]
    fused_n = sum(1 for s in fused_prog if s.via == "fused_matmul")
    fp = get_planner().fingerprint
    sig = next(s for s, r in logger.plan_records.items()
               if r.get("consumer") == "dp-grad")
    f_frac, f_wire = exposure_fraction(f_expo)

    # sequenced arm: the PR 8 program (same phases, via=xla) replayed
    # through a plan-cache entry under the SAME mesh fingerprint
    seq_prog = tuple(_dc.replace(s, via="xla", compute=None)
                     if s.via == "fused_matmul" else s for s in fused_prog)
    cache_dir = tempfile.mkdtemp(prefix="dstpu_t3_cache_")
    try:
        plan = Plan(fingerprint=fp.digest())
        plan.decisions[sig] = PlanDecision(
            impl="program", block=impl[1], source="measured", est_us=1.0,
            program=seq_prog)
        PlanCache(cache_dir).store(fp, plan)
        eng2, s_tot, s_expo, s_losses = run({"mode": "static",
                                             "cache_dir": cache_dir,
                                             "dcn_axes": ["dp_outer"]})
        assert eng2._dp_grad_impl[0] == "program"
        assert all(s.via != "fused_matmul" for s in eng2._dp_grad_impl[2])
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    s_frac, s_wire = exposure_fraction(s_expo)

    # bitwise proof: fused-exact vs sequenced-exact through the executor
    exact_fused = tuple(_dc.replace(s, wire_dtype="exact", block=None)
                        for s in fused_prog)
    exact_seq = tuple(_dc.replace(s, wire_dtype="exact", block=None)
                      for s in seq_prog)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                ("dp_outer", "ep"))
    probe = jnp.linspace(-1.0, 1.0, 1 << 16, dtype=jnp.float32)

    def run_prog(prog):
        def f(v):
            return run_collective_program(v, prog)[0]

        return np.asarray(jax.jit(shard_map_nocheck(
            f, mesh, in_specs=P(), out_specs=P()))(probe))

    bitwise = bool(np.array_equal(run_prog(exact_fused), run_prog(exact_seq)))
    logger.reset()

    return {"metric": "fused_exposed_fraction",
            "value": round(f_frac, 4) if f_frac is not None else None,
            "unit": "exposed-wire-fraction",
            "vs_baseline": None,
            "fused_program": program_summary(fused_prog),
            "fused_phases": fused_n,
            "sequenced_exposed_fraction": (round(s_frac, 4)
                                           if s_frac is not None else None),
            "fused_wire_bytes": f_wire, "sequenced_wire_bytes": s_wire,
            "equal_wire_bytes": f_wire == s_wire,
            "fused_exact_bitwise_eq_sequenced_exact": bitwise,
            "hop_exposure": {k: dict(v) for k, v in f_expo.items()},
            "final_loss": round(losses[-1], 6),
            "final_loss_sequenced": round(s_losses[-1], 6),
            "devices": len(jax.devices()),
            "device": jax.devices()[0].platform}


def program_compiler_bench():
    """Rung cp (collective-program compiler, comm/planner/compiler.py):
    searched program vs the best FIXED-MENU program on a 3-axis
    ici x ici x dcn mesh the five-candidate menu was never written for
    (dp_outer=8 forced DCN, ep=2, tp=2 slice-local — 32 virtual devices).
    The menu's strongest arm keeps an O(p) int8_ef ring on the 8-wide DCN
    core; the compiler's beam finds the O(log p) tree core the grammar
    exposes. Metric: exposed DCN wire time per step from the shared cost
    model — the sum of the per-phase alpha/beta estimates over the phases
    that touch ``fp.dcn_axes``, menu-best over searched-best (higher =
    searched wins; deterministic model arithmetic, no wall clock). The
    acceptance bar is >= 1.3x on DCN exposure and >= 1.15x modeled
    end-to-end; an executor probe on the real 32-device mesh proves the
    searched program computes the same mean all-reduce (allclose vs flat
    XLA — the tree core reassociates, so bitwise is not the contract)."""
    from deepspeed_tpu.comm.compressed import run_collective_program
    from deepspeed_tpu.comm.planner import (CollectivePlanner,
                                            compile_programs,
                                            legacy_menu_programs, make_site,
                                            program_summary, reset_planner)
    from deepspeed_tpu.parallel import Topology, TopologySpec
    from deepspeed_tpu.utils.shard_map_compat import shard_map_nocheck
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 32:
        return {"metric": "program_search_dcn_speedup", "value": None,
                "unit": "ratio", "vs_baseline": None,
                "error": "needs a 32-device mesh"}

    reset_planner()
    topo = Topology(TopologySpec(ep=2, tp=2))  # dp_outer=8, ep=2, tp=2
    pl = CollectivePlanner("static", topology=topo, use_cache=False,
                           dcn_axes=["dp_outer"])
    fp = pl.fingerprint
    site = make_site(op="all_reduce", shape=(1 << 16,), dtype="float32",
                     axes=("dp_outer", "ep", "tp"), consumer="dp-grad")

    def dcn_exposure(prog):
        # the same payload walk as estimate_program, summing only the
        # phases whose span touches a forced-DCN axis
        n, t = float(site.nbytes), 0.0
        for st in prog:
            dt, n = pl.cost.estimate_phase(site, st, n)
            if any(a in fp.dcn_axes for a in st.axes):
                t += dt
        return t

    menu = [(p, pl.cost.estimate_program(site, p))
            for p in legacy_menu_programs(site, pl.cost, block=pl.block)]
    menu = [(p, e) for p, e in menu if np.isfinite(e)]
    menu.sort(key=lambda pe: pe[1])
    beam = compile_programs(site, pl.cost, block=pl.block,
                            beam_width=pl.beam_width)
    if not menu or not beam:
        return {"metric": "program_search_dcn_speedup", "value": None,
                "unit": "ratio", "vs_baseline": None,
                "error": f"menu={len(menu)} beam={len(beam)} candidates"}
    menu_prog, menu_est = menu[0]
    searched_prog, searched_est = beam[0]
    menu_dcn, searched_dcn = dcn_exposure(menu_prog), dcn_exposure(searched_prog)

    # executor probe: the searched winner computes the same MEAN all-reduce
    # (the dp-grad program convention) on the REAL 32-device mesh (exact
    # wire; the tree core reassociates the sum, so the contract is
    # allclose, not bitwise)
    import dataclasses as _dc

    exact = tuple(_dc.replace(s, wire_dtype="exact", block=None)
                  for s in searched_prog)
    mesh = Mesh(np.array(jax.devices()[:32]).reshape(8, 2, 2),
                ("dp_outer", "ep", "tp"))
    probe = jnp.linspace(-1.0, 1.0, 1 << 16, dtype=jnp.float32)

    def _ranked(v):
        # per-rank distinct payload: a replicated probe would make the mean
        # an identity and prove nothing
        r = (jax.lax.axis_index("dp_outer") * 4.0
             + jax.lax.axis_index("ep") * 2.0 + jax.lax.axis_index("tp"))
        return v * (1.0 + 0.01 * r)

    def prog_fn(v):
        return run_collective_program(_ranked(v), exact)[0]

    def flat_fn(v):
        return jax.lax.pmean(_ranked(v), ("dp_outer", "ep", "tp"))

    got = np.asarray(jax.jit(shard_map_nocheck(
        prog_fn, mesh, in_specs=P(), out_specs=P()))(probe))
    want = np.asarray(jax.jit(shard_map_nocheck(
        flat_fn, mesh, in_specs=P(), out_specs=P()))(probe))
    ok = bool(np.allclose(got, want, rtol=1e-5, atol=1e-5))

    dcn_ratio = menu_dcn / searched_dcn if searched_dcn else None
    return {"metric": "program_search_dcn_speedup",
            "value": round(dcn_ratio, 4) if dcn_ratio else None,
            "unit": "menu-over-searched-dcn-exposure",
            "vs_baseline": None,
            "modeled_speedup": round(menu_est / searched_est, 4),
            "menu_program": program_summary(menu_prog),
            "searched_program": program_summary(searched_prog),
            "menu_est_us": round(menu_est * 1e6, 1),
            "searched_est_us": round(searched_est * 1e6, 1),
            "menu_dcn_us": round(menu_dcn * 1e6, 1),
            "searched_dcn_us": round(searched_dcn * 1e6, 1),
            "searched_uses_tree": any(s.via == "tree"
                                      for s in searched_prog),
            "beam_width": len(beam),
            "executor_allclose_flat_xla": ok,
            "devices": len(jax.devices()),
            "device": jax.devices()[0].platform}


def telemetry_bench():
    """Rung ob (telemetry spine, deepspeed_tpu/telemetry/): the spine's own
    cost, since it rides every step when enabled — span record overhead
    (ns/span, enabled AND the disabled no-op path), flight-recorder dump
    latency on a full ring (bounds what a watchdog expiry adds before the
    hangdump), and registry scrape time for a realistic series count (the
    /metrics handler's per-request cost)."""
    import shutil as _shutil
    import tempfile

    from deepspeed_tpu.telemetry import (FlightRecorder, MetricsRegistry,
                                         SpanTracer)

    tr = SpanTracer(enabled=True, max_spans=8192)
    for _ in range(2000):  # warm the allocator/deque path
        with tr.span("x"):
            pass
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("x"):
            pass
    span_ns = (time.perf_counter() - t0) / n * 1e9

    off = SpanTracer(enabled=False)
    t0 = time.perf_counter()
    for _ in range(n):
        with off.span("x"):
            pass
    off_ns = (time.perf_counter() - t0) / n * 1e9

    # flight dump on a FULL ring: 32 steps x 8 phase spans + metrics
    phases = ("data/draw", "data/shape", "compute/dispatch", "compute/drain",
              "metrics/report", "resilience/post_step", "serve/admit",
              "serve/decode")
    d = tempfile.mkdtemp(prefix="dstpu_ob_")
    try:
        ftr = SpanTracer(enabled=True)  # fresh: the ring must hold 32 real
        fl = FlightRecorder(ftr, d, steps=32)  # steps, not the bench's 50k spans
        for step in range(32):
            for ph in phases:
                with ftr.span(ph):
                    pass
            fl.record_step(step, step_time_s=0.01,
                           metrics={"loss": 1.0, "grad_norm": 0.5})
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            path = fl.dump("bench")
            best = min(best, time.perf_counter() - t0)
        dump_ms = best * 1e3
        dump_kb = os.path.getsize(path) / 1024
    finally:
        _shutil.rmtree(d, ignore_errors=True)

    # registry scrape: phase histograms + labeled counters + a collector,
    # roughly what a training+serving process exposes
    reg = MetricsRegistry()
    hist = reg.histogram("dstpu_step_phase_seconds", "phases")
    for ph in phases:
        for i in range(100):
            hist.observe(1e-4 * (i + 1), phase=ph)
    ops = reg.counter("dstpu_comm_wire_bytes_total", "wire")
    for op in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "ring_embed_gather", "program_reduce_scatter"):
        ops.inc(1 << 20, op=op)
    reg.register_collector("x", lambda: [
        ("dstpu_serving_ttft_p50_seconds", "gauge", "",
         [("", {"replica": "0"}, 0.01)])])
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        text = reg.exposition()
        best = min(best, time.perf_counter() - t0)
    scrape_ms = best * 1e3
    series = sum(1 for line in text.splitlines()
                 if line and not line.startswith("#"))

    return {"metric": "telemetry_span_overhead_ns",
            "value": round(span_ns, 1), "unit": "ns/span",
            "vs_baseline": None,
            "span_disabled_ns": round(off_ns, 2),
            "flight_dump_ms": round(dump_ms, 3),
            "flight_dump_kb": round(dump_kb, 1),
            "registry_scrape_ms": round(scrape_ms, 3),
            "registry_series": series,
            "device": jax.devices()[0].platform}


def memory_telemetry_bench():
    """Rung mem (device-memory telemetry + collective flight recorder,
    PR 10): the recording costs that ride every step when enabled —
    collective-ring record overhead (ns/launch, enabled AND the disabled
    no-op path the default tree pays), ``device.memory_stats()`` read
    latency (the per-step HBM gauge cost; stays host-side — no device
    sync), and one compile-time ``memory_analysis()`` extraction with its
    reported breakdown. Gate direction: lower-is-better on the headline
    overhead (a recorder that starts allocating per launch must fail CI)."""
    from deepspeed_tpu.telemetry.collective import CollectiveRecorder

    rec = CollectiveRecorder(enabled=True, max_records=512)
    for _ in range(2000):  # warm the deque/dict path
        rec.record("all_reduce", shape=(1024, 1024), dtype="float32",
                   axes=("dp",))
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.record("all_reduce", shape=(1024, 1024), dtype="float32",
                   axes=("dp",))
    record_ns = (time.perf_counter() - t0) / n * 1e9

    off = CollectiveRecorder(enabled=False)
    t0 = time.perf_counter()
    for _ in range(n):
        off.record("all_reduce", shape=(1024, 1024), dtype="float32",
                   axes=("dp",))
    off_ns = (time.perf_counter() - t0) / n * 1e9

    # memory_stats read latency: the per-step gauge cost. On CPU the call
    # returns None — the latency of the (call, None) path is still the
    # honest number for what a CPU smoke run pays before self-disabling.
    dev = jax.local_devices()[0]
    jnp.ones((8,)).block_until_ready()  # backend up before timing
    m = 2000
    t0 = time.perf_counter()
    stats = None
    for _ in range(m):
        stats = dev.memory_stats()
    stats_us = (time.perf_counter() - t0) / m * 1e6

    # compile-time memory_analysis on a small-but-real jitted step
    def step(p, b):
        h = jnp.tanh(b @ p["w1"])
        return p, jnp.mean((h @ p["w2"]) ** 2)

    params = {"w1": jnp.ones((256, 512), jnp.float32),
              "w2": jnp.ones((512, 64), jnp.float32)}
    batch = jnp.ones((32, 256), jnp.float32)
    exe = jax.jit(step).lower(params, batch).compile()
    t0 = time.perf_counter()
    ma = exe.memory_analysis()
    analysis_us = (time.perf_counter() - t0) * 1e6
    breakdown = {k: int(getattr(ma, k, 0)) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")} \
        if ma is not None else {}

    return {"metric": "collective_ring_overhead_ns",
            "value": round(record_ns, 1), "unit": "ns/launch",
            "vs_baseline": None,
            "record_disabled_ns": round(off_ns, 2),
            "memory_stats_us": round(stats_us, 3),
            "memory_stats_available": stats is not None,
            "memory_analysis_us": round(analysis_us, 1),
            "exec_memory": breakdown,
            "ring_records": len(rec.snapshot()),
            "device": jax.devices()[0].platform}


def static_audit_bench():
    """Rung sa (static graph auditor, deepspeed_tpu/analysis/): the
    auditor's own wall-time, since the compile-time hook rides every
    ``engine.compile()`` when enabled — (1) a full four-check audit of the
    engine's compiled train step (trace reuse + HLO walk + reconciliation
    against the ledger), and (2) of the fused serving decode step
    (``inference/v2 decode_loop``, the scanned whole-model program — the
    deepest jaxpr the repo stages). Programs are staged/compiled ONCE
    outside the timed region; each rep pays what the hook pays: lower +
    jaxpr checks + HLO parse + reconciliation. Gate direction:
    lower-is-better on the train-step audit (an auditor that starts
    re-compiling or quadratic-walking must fail CI). Findings counts ride
    along — the clean train step must stay at zero errors."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.analysis import AuditOptions, audit_step

    dim, batch = 256, 64
    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(0, 0.05, (dim, 4 * dim)),
                                jnp.float32),
              "w2": jnp.asarray(rng.normal(0, 0.05, (4 * dim, dim)),
                                jnp.float32),
              "w3": jnp.asarray(rng.normal(0, 0.05, (dim, 10)), jnp.float32)}

    def loss_fn(p, b, rng=None):
        h = jnp.tanh(jnp.tanh(b["x"] @ p["w1"]) @ p["w2"])
        logits = h @ p["w3"]
        return jnp.mean(jax.nn.logsumexp(logits, -1)
                        - jnp.take_along_axis(logits, b["y"][:, None],
                                              1)[:, 0])

    engine, *_ = ds.initialize(
        model=loss_fn, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": batch,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10**9})
    b = engine._shape_batch(
        {"x": jnp.asarray(rng.normal(size=(batch, dim)), jnp.float32),
         "y": jnp.asarray(rng.integers(0, 10, batch), jnp.int32)})
    step_rng = jax.random.PRNGKey(0)
    traced = engine._train_step.trace(engine.state, b, step_rng)
    exe = traced.lower().compile()  # staged once; the hook reuses it too
    ledger = dist.get_comms_logger()
    axis_sizes = {str(k): int(v)
                  for k, v in dict(engine.topo.mesh.shape).items()}

    def one_train_audit():
        return audit_step(traced, compiled=exe, label="train_step",
                          options=AuditOptions(), axis_sizes=axis_sizes,
                          plan_records=ledger.plan_records, ledger=ledger)

    rep = one_train_audit()
    best_train = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        one_train_audit()
        best_train = min(best_train, time.perf_counter() - t0)

    # the serving decode step: the scanned fused decode program
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model import decode_loop
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            num_kv_heads=2, max_seq_len=128,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    mp = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    v2 = InferenceEngineV2(model, mp, RaggedInferenceEngineConfig(
        token_budget=16, max_ragged_sequence_count=4, max_chunk_size=8,
        num_kv_blocks=32, kv_block_size=8, max_blocks_per_seq=8,
        dtype="float32"))
    kv_k, kv_v = v2.kv.pool_args()
    S, B = 4, 8
    dec_args = (v2.params, v2.cfg, kv_k, kv_v,
                jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.int32),
                jnp.zeros((S, B), jnp.int32), jnp.ones((S,), bool),
                jax.random.PRNGKey(1), jnp.float32(1.0))
    dec_kw = dict(n_steps=8, attn_impl="einsum", greedy=True)
    dec_traced = decode_loop.trace(*dec_args, **dec_kw)
    dec_exe = dec_traced.lower().compile()

    def one_decode_audit():
        return audit_step(dec_traced, compiled=dec_exe, label="decode_step",
                          options=AuditOptions())

    dec_rep = one_decode_audit()
    best_dec = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        one_decode_audit()
        best_dec = min(best_dec, time.perf_counter() - t0)

    return {"metric": "static_audit_train_ms",
            "value": round(best_train * 1e3, 2), "unit": "ms/audit",
            "vs_baseline": None,
            "audit_decode_ms": round(best_dec * 1e3, 2),
            "train_findings": rep.counts(),
            "train_hlo_collectives": rep.context.get("hlo_collectives"),
            "train_unplanned": rep.context.get("unplanned_collectives"),
            "decode_findings": dec_rep.counts(),
            "decode_hlo_collectives": dec_rep.context.get("hlo_collectives"),
            "decode_unplanned": dec_rep.context.get("unplanned_collectives"),
            "device": jax.devices()[0].platform}


def control_bench():
    """Rung at (control plane, deepspeed_tpu/control/): (1) Autotuner v2
    probe cost — wall-clock per candidate through the in-process
    engine-warmup path (grid over gas x compression, cache off so every
    probe is real), the number an operator budgets tuning time with; and
    (2) the supervisor decision loop's per-step cost with control ARMED
    but no signal firing (the steady-state tax every training step pays:
    three rule evaluations through the flap guard) vs the disarmed path's
    single attribute check. Gate direction: lower-is-better on the armed
    decision loop — a supervisor that starts re-reading health tables or
    allocating per step must fail CI."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.control import ControlAutotuner
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)) * 0.05,
                               jnp.float32)}

    def loss(p, b, rng=None):
        return jnp.mean((b @ p["w"]) ** 2)

    def batch_fn(gbs):
        r = np.random.default_rng(0)
        return jnp.asarray(r.normal(size=(max(int(gbs), 8), 64)), np.float32)

    base = {"train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 10**9}
    at = ControlAutotuner(base, dims=("gas", "compression"),
                          warmup_steps=1, measure_steps=1,
                          tuner_type="gridsearch", use_cache=False,
                          probe_programs=False)
    t0 = time.perf_counter()
    at.tune(loss, params, batch_fn)
    probe_ms = (time.perf_counter() - t0) / max(1, at.probes_run) * 1e3

    # decision loop armed (no signal fires) vs the disarmed attribute check
    eng, *_ = ds.initialize(model=loss, model_parameters=params,
                            config={**base, "control": True})
    sup = eng.control
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        sup.on_step()
    armed_ns = (time.perf_counter() - t0) / n * 1e9
    eng_off, *_ = ds.initialize(model=loss, model_parameters=params,
                                config=dict(base))
    t0 = time.perf_counter()
    acc = 0
    for _ in range(n):
        if eng_off.control is not None:  # the entire disabled-path cost
            acc += 1
    off_ns = (time.perf_counter() - t0) / n * 1e9

    return {"metric": "control_decide_ns",
            "value": round(armed_ns, 1), "unit": "ns/step",
            "vs_baseline": None,
            "decide_off_ns": round(off_ns, 2),
            "autotune_probe_ms": round(probe_ms, 1),
            "autotune_probes": at.probes_run,
            "autotune_grid": at.grid_size,
            "autotune_winner": at.best["name"],
            "ledger_entries": len(sup.ledger),
            "device": jax.devices()[0].platform}


def chaos_soak_bench():
    """Rung cz (chaos engine, ISSUE 15): a seeded full-stack chaos soak —
    serving and training drills run under one deterministic ChaosSchedule
    spanning every fault layer (transport: object-store PUT/GET errors,
    torn beacons, plan-cache read errors, snapshot-commit I/O errors;
    serving: replica kill, KV exhaustion, slow prefill, dropped token
    delivery; control: stale health rows, flapping straggler; training:
    injected NaN loss -> sentinel rollback). The row VALUE is the number of
    distinct fault classes fired (deterministic, gated tight), and the
    rung itself asserts the survival invariants: zero lost response
    handles, zero duplicate delivered tokens, post-rollback loss bitwise
    equal to the fault-free run, and a doctor report that names every
    injected fault."""
    import random as _random
    import shutil as _shutil
    import tempfile

    import deepspeed_tpu as ds
    from deepspeed_tpu import doctor
    from deepspeed_tpu.comm.planner.cache import PlanCache
    from deepspeed_tpu.comm.planner.ir import Plan, PlanDecision
    from deepspeed_tpu.comm.planner.topo import MeshFingerprint
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)
    from deepspeed_tpu.runtime.resilience import (ChaosEvent, ChaosSchedule,
                                                  configure_chaos, get_chaos)
    from deepspeed_tpu.runtime.resilience.heartbeat import (
        HealthTable, ObjectStoreHeartbeatTransport)
    from deepspeed_tpu.serving import (FINISH_EOS, FINISH_LENGTH, LLMServer,
                                       ReplicaRouter, Request)
    from deepspeed_tpu.utils.retry import (clear_retry_log,
                                           retry_log_snapshot)

    SEED = 1337
    rng = _random.Random(SEED)
    work = tempfile.mkdtemp(prefix="dstpu_cz_")
    artifacts = os.path.join(work, "artifacts")
    os.makedirs(artifacts)
    t_start = time.perf_counter()
    configure_chaos(None)
    clear_retry_log()
    try:
        # ---- fault-free training reference (runs BEFORE any chaos) ------
        dim, batch, nsteps = 64, 32, 10
        prng = np.random.default_rng(SEED)
        params0 = {"w": jnp.asarray(prng.normal(0, 0.05, (dim, dim)),
                                    jnp.float32)}

        def loss_fn(p, b):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

        batches = [{"x": jnp.asarray(prng.normal(size=(batch, dim)),
                                     jnp.float32),
                    "y": jnp.asarray(prng.normal(size=(batch, dim)),
                                     jnp.float32)}
                   for _ in range(4)]
        base_cfg = {"train_micro_batch_size_per_gpu": batch,
                    "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
                    "steps_per_print": 10**9, "seed": SEED}

        def run_training(extra_cfg):
            import copy as _copy

            eng, *_ = ds.initialize(
                model=loss_fn,
                model_parameters=jax.tree.map(jnp.copy, params0),
                config={**_copy.deepcopy(base_cfg), **extra_cfg})
            losses = {}
            while eng.global_steps < nsteps:
                step = eng.global_steps
                losses[step + 1] = float(np.asarray(
                    eng.train_batch(batches[step % len(batches)])))
            return eng, losses

        _, ref_losses = run_training({})

        # ---- phase A: serving + transport + control drills --------------
        # seeded schedule: arming indices drawn per class from Random(SEED)
        schedule = ChaosSchedule([
            ChaosEvent("transport_put_error", "heartbeat.put",
                       at=rng.randrange(2, 6), count=2),
            ChaosEvent("transport_get_error", "heartbeat.get",
                       at=rng.randrange(1, 4), count=2),
            ChaosEvent("torn_beacon", "heartbeat.put",
                       at=rng.randrange(8, 14)),
            ChaosEvent("plan_cache_error", "plan_cache.load",
                       at=0, count=2),
            ChaosEvent("replica_kill", "replica0",
                       at=rng.randrange(18, 26)),
            ChaosEvent("kv_exhaustion", "scheduler.admit",
                       at=rng.randrange(2, 5), count=3),
            ChaosEvent("slow_prefill", "replica0",
                       at=rng.randrange(1, 3), param=0.02),
            ChaosEvent("drop_token", "replica0",
                       at=rng.randrange(8, 14), count=2),
            ChaosEvent("stale_health", "health.read",
                       at=rng.randrange(1, 3)),
            ChaosEvent("flap_straggler", "health.read",
                       at=rng.randrange(3, 6), count=4, param=1.0),
        ], seed=SEED)
        configure_chaos(schedule)

        # plan-cache drill: a stored plan survives transient read errors
        fp = MeshFingerprint(platform="cpu", device_kind="cpu", n_devices=1,
                             n_processes=1, axis_sizes=(("dp", 1),),
                             dcn_axes=())
        pc = PlanCache(os.path.join(work, "plans"))
        plan = Plan(fingerprint=fp.digest())
        plan.decisions["site"] = PlanDecision(impl="xla", est_us=1.0)
        pc.store(fp, plan)
        assert pc.load(fp) is not None, "plan cache lost to transient errors"

        # serving drill: 2 replicas over an object-store heartbeat bucket
        cfg = TransformerConfig(vocab_size=97, hidden_size=48,
                                intermediate_size=96, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=256,
                                dtype=jnp.float32, norm="rmsnorm",
                                activation="swiglu")
        model = TransformerLM(cfg)
        mparams = model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]

        def make_engine():
            return InferenceEngineV2(model, mparams,
                                     RaggedInferenceEngineConfig(
                                         token_budget=32,
                                         max_ragged_sequence_count=4,
                                         max_chunk_size=16, num_kv_blocks=96,
                                         kv_block_size=8,
                                         max_blocks_per_seq=16,
                                         dtype="float32"))

        transport = ObjectStoreHeartbeatTransport(
            os.path.join(work, "bucket"))
        r0 = LLMServer(make_engine(), replica_id=0,
                       heartbeat_interval_s=0.02,
                       resume_checkpoint_tokens=8)
        r1 = LLMServer(make_engine(), replica_id=1,
                       heartbeat_interval_s=0.02,
                       resume_checkpoint_tokens=8)
        router = ReplicaRouter([r0, r1], transport=transport,
                               dead_after_s=0.6).start()
        table = HealthTable(transport, dead_after_s=0.6)
        streams = {}

        def make_stream(i):
            streams[i] = []
            return lambda tok, resp: streams[i].append(tok)

        n_req, mnt = 8, 40
        resps = [router.submit(
            Request(np.asarray(prng.integers(1, cfg.vocab_size, 10),
                               np.int32),
                    max_new_tokens=mnt, stream=make_stream(i)), block=True)
            for i in range(n_req)]
        deadline = time.monotonic() + 600
        while (not all(r.done for r in resps)
               and time.monotonic() < deadline):
            router.check()      # the dead-replica takeover + resume path
            table.read()        # the control-layer stale/flap consults
            time.sleep(0.05)

        lost = [i for i, r in enumerate(resps) if not r.done]
        failed = [i for i, r in enumerate(resps)
                  if r.finish_reason not in (FINISH_EOS, FINISH_LENGTH)]
        assert not lost, f"lost response handles: {lost}"
        assert not failed, f"failed response handles: {failed}"
        dup_tokens = sum(1 for i, r in enumerate(resps)
                         if streams[i] != r.tokens)
        assert dup_tokens == 0, "stream delivery diverged from tokens " \
            "(duplicate or lost deliveries)"
        requeues = router.requeues
        resumed = sum(1 for r in resps if r.requeues and r._ckpt_len)
        assert requeues > 0 and resumed > 0, \
            "the replica kill never exercised the resume path"
        router.drain(timeout=600)
        fired_a = schedule.all_fired()

        # ---- phase B: training drill (chaos: config block wiring) -------
        chaos_cfg = {
            "chaos": {"enabled": True, "seed": SEED,
                      "events": [{"kind": "snapshot_io_error",
                                  "site": "snapshot.commit",
                                  "at": 0, "count": 2}],
                      "training": {"enabled": True,
                                   "nan_loss_at_steps": [3]}},
            "resilience": {"enabled": True,
                           "snapshot_dir": os.path.join(work, "snaps"),
                           "snapshot_interval": 2,
                           "sentinel": {"nan_streak": 1}}}
        eng, chaos_losses = run_training(chaos_cfg)
        assert eng.resilience.rollbacks == 1, "injected NaN never rolled back"
        fired_b = get_chaos().all_fired()
        # post-rollback trajectory must match the fault-free run bitwise:
        # the rollback restored the exact snapshot, and batches are indexed
        # by global_steps, so the re-stepped losses coincide
        post = {s: l for s, l in chaos_losses.items()
                if s in ref_losses and s > 4}
        mismatch = {s: (l, ref_losses[s]) for s, l in post.items()
                    if l != ref_losses[s]}
        assert not mismatch, f"post-rollback losses diverged: {mismatch}"

        # ---- post-mortem: the doctor must name every injected fault -----
        # canonical manifest encoding (ChaosSchedule.to_manifest): merge
        # phase A's and phase B's trails under one schedule file
        man = schedule.to_manifest()
        man_b = get_chaos().to_manifest()
        man["events"] += man_b["events"]
        man["fired"] = all_fired = fired_a + fired_b
        classes = sorted({e["kind"] for e in all_fired})
        with open(os.path.join(artifacts, "chaos-schedule.json"), "w") as f:
            json.dump(man, f, indent=1)
        retries = retry_log_snapshot()
        with open(os.path.join(artifacts, "flightdump-0.json"), "w") as f:
            json.dump({"reason": "preempt_drain", "rank": 0, "pid": os.getpid(),
                       "sequence": 1, "wall_time": time.time(),
                       "last_phase": None, "open_spans": [],
                       "inflight_spans": [], "steps": [],
                       "retries": retries}, f)
        report = doctor.diagnose(artifacts)
        named = [k for k in classes
                 if any(f"chaos drill injected {k}" in ev
                        for ev in report["evidence"])]
        missing = sorted(set(classes) - set(named))
        assert not missing, f"doctor failed to name injected faults: {missing}"

        retry_sites = sorted({e["site"] for e in retries})
        wall = time.perf_counter() - t_start
        return {"metric": "chaos_soak_fault_classes", "value": len(classes),
                "unit": "classes", "vs_baseline": None, "seed": SEED,
                "classes_fired": classes,
                "lost_handles": len(lost), "failed_handles": len(failed),
                "duplicate_token_streams": dup_tokens,
                "requeues": requeues, "resumed_requests": resumed,
                "rollbacks": eng.resilience.rollbacks,
                "post_rollback_loss_match": not mismatch,
                "doctor_named": len(named),
                "doctor_verdict": report["verdict"],
                "retries_total": len(retries), "retry_sites": retry_sites,
                "served_requests": n_req, "tokens_per_request": mnt,
                "wall_s": round(wall, 2),
                "device": jax.devices()[0].platform}
    finally:
        configure_chaos(None)
        clear_retry_log()
        _shutil.rmtree(work, ignore_errors=True)


def fleet_serving_bench():
    """Rung fs (fleet tier, ISSUE 19): a chaos-soaked elastic-serving soak —
    a FleetManager-run replica fleet under bursty multi-tenant open-loop
    traffic. Mid-burst a seeded ``replica_kill`` takes out a JOINED replica
    (the router requeue-resumes its work onto the survivor, preserving
    tenant identity); the survivor's SLA-violation rate then trips the
    ControlSupervisor's ``rule_sla``, whose registered ``scale_fn`` IS
    ``FleetManager.scale_out`` — the joining replica walks SPAWNING →
    WARMING → JOINED applying the cached autotune winner with ZERO probes
    (a ``replica_slow_warm`` drill stalls its bring-up to prove the warm
    gate holds), and once the burst drains, sustained under-utilization
    scales the fleet back in through the flap guard. The row VALUE is the
    fleet's delivered tok/s in the post-join window; the hard gates ride
    in-process: ZERO lost requests across the kill, the kill preceding a
    measurable tok/s rise at join, a zero-probe joiner, bounded p99 TTFT,
    and a doctor report that names the kill and both scale events."""
    import random as _random
    import shutil as _shutil
    import tempfile

    from deepspeed_tpu import doctor
    from deepspeed_tpu.control.ledger import ControlLedger
    from deepspeed_tpu.control.supervisor import ControlSupervisor
    from deepspeed_tpu.fleet import JOINED, FleetManager, SLAClass, TenancyMap
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)
    from deepspeed_tpu.runtime.config import (ControlConfig,
                                              ControlGuardConfig,
                                              ControlSupervisorConfig)
    from deepspeed_tpu.runtime.resilience import (ChaosEvent, ChaosSchedule,
                                                  configure_chaos)
    from deepspeed_tpu.runtime.resilience.heartbeat import (
        ObjectStoreHeartbeatTransport)
    from deepspeed_tpu.serving import (FINISH_EOS, FINISH_LENGTH, LLMServer,
                                       Request, ServerClosed, ServerOverloaded)

    SEED = 4119
    rng = _random.Random(SEED)
    prng = np.random.default_rng(SEED)
    work = tempfile.mkdtemp(prefix="dstpu_fs_")
    artifacts = os.path.join(work, "artifacts")
    os.makedirs(artifacts)
    t_start = time.perf_counter()
    configure_chaos(None)
    mgr = None
    try:
        cfg = TransformerConfig(vocab_size=97, hidden_size=48,
                                intermediate_size=96, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=256,
                                dtype=jnp.float32, norm="rmsnorm",
                                activation="swiglu")
        model = TransformerLM(cfg)
        mparams = model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]

        def make_engine():
            return InferenceEngineV2(model, mparams,
                                     RaggedInferenceEngineConfig(
                                         token_budget=32,
                                         max_ragged_sequence_count=4,
                                         max_chunk_size=16, num_kv_blocks=96,
                                         kv_block_size=8,
                                         max_blocks_per_seq=16,
                                         dtype="float32"))

        # multi-tenant SLA ladder: bronze/silver deadlines sit BELOW the
        # latency a kill imposes (queue wait at the victim + stale-beacon
        # detection + requeue + re-serve), so the post-kill survivor's
        # finishes deterministically violate them — the signal rule_sla
        # scales out on. Gold stays loose: the premium class should ride
        # through the kill without a violation
        tenancy = TenancyMap([SLAClass("gold", weight=4.0, deadline_s=8.0),
                              SLAClass("silver", weight=2.0, deadline_s=0.9),
                              SLAClass("bronze", weight=1.0, deadline_s=0.45)])

        def factory(rid):
            return LLMServer(make_engine(), replica_id=rid,
                             policy="deadline", tenancy=tenancy,
                             heartbeat_interval_s=0.02,
                             resume_checkpoint_tokens=8)

        ledger = ControlLedger(max_entries=512)
        sup = ControlSupervisor(ControlConfig(
            enabled=True,
            supervisor=ControlSupervisorConfig(
                interval_steps=1, sla_guard=True,
                sla_violation_rate=0.25, sla_min_tracked=2,
                straggler_replan=False, memory_guard=False,
                rollback_degrade=False),
            # trigger_streak=1: serving finishes arrive in fused-chunk
            # bursts, so consecutive 6-step ticks can straddle a burst and
            # see dt < sla_min_tracked — a 2-streak would reset right in
            # the middle of real pressure; the cooldown still stops flaps
            # cooldown 0.5s: if pressure fired once pre-kill (rejected at
            # capacity), the reconcile re-arms the rule and the refire
            # must land inside the few-second post-kill burst window
            guard=ControlGuardConfig(trigger_streak=1, clear_streak=2,
                                     cooldown_s=0.5, budget=64,
                                     budget_window_s=3600.0)),
            ledger=ledger)
        # max_replicas=2: after the kill the fleet is 1, the SLA scale-out
        # restores 2 (= capacity) — further pressure exercises the
        # at-capacity shed fallback instead of unbounded growth. The
        # manager gets its OWN guard: scale-in should take sustained
        # under-utilization (3 consecutive low-load polls), not inherit
        # the deliberately hair-triggered SLA guard above
        from deepspeed_tpu.control.guard import FlapGuard
        mgr = FleetManager(factory, supervisor=sup, min_replicas=1,
                           max_replicas=2, scale_in_low_watermark=0.5,
                           drain_timeout_s=600.0,
                           guard=FlapGuard(trigger_streak=3, clear_streak=2,
                                           cooldown_s=2.0, budget=64),
                           autotune_cache_dir=os.path.join(work, "winners"))

        # seeded chaos: kill replica 0 mid-burst (armed on ITS engine-step
        # count), and stall the future joiner's warm-up — the warm gate
        # must keep traffic off it for the whole stall
        schedule = ChaosSchedule([
            ChaosEvent("replica_kill", "replica0", at=rng.randrange(10, 16)),
            ChaosEvent("replica_slow_warm", "replica2", at=0, param=0.05),
        ], seed=SEED)
        configure_chaos(schedule)

        transport = ObjectStoreHeartbeatTransport(os.path.join(work,
                                                               "bucket"))
        router = mgr.start(2, transport=transport, dead_after_s=0.6)
        # replica 0 probed the serving winner and cached it; replica 1
        # joined from cache — the scale-out joiner must too
        for h in mgr.handles.values():
            sup.attach_server(h.server, interval_steps=6,
                              scale_fn=mgr.scale_out)

        mnt = 12
        tenants_cycle = ["gold", "bronze", "silver", "bronze"]
        resps, resp_tenant, shed = [], [], 0
        t_kill = t_join = scale_in_rid = None
        after_join = 0
        max_requests, tail_after_join = 240, 24

        def submit_one(i):
            nonlocal shed
            t = tenants_cycle[i % len(tenants_cycle)]
            req = Request(np.asarray(prng.integers(1, cfg.vocab_size, 8),
                                     np.int32),
                          max_new_tokens=mnt, tenant=t)
            try:
                r = router.submit(req, block=True, timeout=2.0)
            except (ServerOverloaded, ServerClosed):
                shed += 1       # shed by the tenant door, NOT lost: the
                return          # client saw a synchronous rejection
            resps.append(r)
            resp_tenant.append(t)

        i = 0
        deadline = time.monotonic() + 900
        while time.monotonic() < deadline:
            if i < max_requests and (t_join is None
                                     or after_join < tail_after_join):
                for _ in range(3):      # open-loop burst: 3 per 20ms tick
                    submit_one(i)
                    i += 1
                    if t_join is not None:
                        after_join += 1
            router.check()
            # the takeover can also happen inside submit() (a shed/closed
            # replica is taken over on the spot), so detect the kill from
            # the router's dead book, not check()'s return value
            if t_kill is None and router.dead_ids():
                t_kill = time.monotonic()
            # reconciles the kill; once the burst tail drains, sustained
            # under-utilization fires the flap-guarded scale-in HERE
            scale_in_rid = mgr.poll() or scale_in_rid
            h2 = mgr.handles.get(2)
            if t_join is None and h2 is not None and h2.state == JOINED:
                t_join = time.monotonic()
            if (all(r.done for r in resps)
                    and (i >= max_requests
                         or (t_join is not None
                             and after_join >= tail_after_join))):
                break
            time.sleep(0.02)
        t_done = time.monotonic()

        # ---- hard gate: zero lost requests across the chaos kill --------
        lost = [j for j, r in enumerate(resps) if not r.done]
        failed = [j for j, r in enumerate(resps)
                  if r.finish_reason not in (FINISH_EOS, FINISH_LENGTH)]
        assert not lost, f"lost response handles: {lost}"
        assert not failed, f"failed response handles: {failed}"
        assert t_kill is not None, "the replica_kill drill never fired"
        assert router.requeues > 0, "the kill never exercised the requeue path"

        # ---- supervisor-driven scale-out, zero-probe warm join ----------
        h2 = mgr.handles.get(2)
        assert h2 is not None and t_join is not None, (
            "rule_sla never scaled the fleet out; ledger="
            + repr([(a["action"], a.get("outcome")) for a in
                    ledger.snapshot()])
            + "; survivor sla="
            + repr([(h.replica_id, h.server.metrics.sla_violations,
                     h.server.metrics.sla_tracked)
                    for h in mgr.handles.values() if h.server is not None])
            + "; e2e p50/p90/max="
            + repr([round(q, 3) for q in (np.percentile(
                [r.e2e_s for r in resps if r.e2e_s is not None] or [0.0],
                [50, 90, 100])).tolist()]))
        assert t_kill < t_join, "kill must precede the scale-out"
        rep2 = h2.report
        assert rep2.autotune_from_cache and rep2.zero_probe_join(), \
            f"joiner ran probes: {rep2.to_params()}"

        # ---- scale-out measurably raises fleet tok/s --------------------
        def tok_s(a, b):
            toks = sum(len(r.tokens) for r in resps
                       if r.finish_time is not None and a <= r.finish_time < b)
            return toks / max(1e-6, b - a)

        tok_down = tok_s(t_kill, t_join)    # one survivor (+ joiner warming)
        tok_up = tok_s(t_join, t_done)      # joiner taking traffic
        assert tok_up > tok_down, \
            f"scale-out did not raise fleet tok/s ({tok_down:.1f} -> " \
            f"{tok_up:.1f})"

        # ---- p99 TTFT held (bounded) under chaos ------------------------
        ttfts = sorted(r.ttft_s for r in resps if r.ttft_s is not None)
        assert ttfts, "no first tokens delivered"
        p99_ttft = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
        assert p99_ttft < 30.0, f"p99 TTFT blew up: {p99_ttft:.1f}s"

        # ---- flap-guarded scale-in once the burst drains ----------------
        for _ in range(300):
            if scale_in_rid is not None:
                break
            scale_in_rid = mgr.poll()
            router.check()
            time.sleep(0.02)
        assert scale_in_rid is not None, "fleet never scaled back in"

        acted = {a["action"] for a in ledger.snapshot()}
        assert {"serving_scale", "replica_join", "replica_reap",
                "serving_scale_in"} <= acted, f"ledger missing actions: {acted}"
        assert schedule.all_fired(), "chaos schedule did not fully fire"

        # ---- post-mortem: the doctor names the kill + both scale events -
        schedule.dump(artifacts)
        with open(os.path.join(artifacts, "flightdump-0.json"), "w") as f:
            json.dump({"reason": "preempt_drain", "rank": 0,
                       "pid": os.getpid(), "sequence": 1,
                       "wall_time": time.time(), "last_phase": None,
                       "open_spans": [], "inflight_spans": [], "steps": [],
                       "retries": [], "control": ledger.snapshot()}, f)
        report = doctor.diagnose(artifacts)
        ev = report["evidence"]
        for needle in ("chaos drill injected replica_kill",
                       "chaos drill injected replica_slow_warm",
                       "serving_scale", "serving_scale_in", "replica_join",
                       "replica_reap"):
            assert any(needle in e for e in ev), \
                f"doctor evidence never names {needle!r}"

        per_tenant = {}
        for t in sorted(set(resp_tenant)):
            tt = sorted(r.ttft_s for r, rt in zip(resps, resp_tenant)
                        if rt == t and r.ttft_s is not None)
            per_tenant[t] = {
                "requests": resp_tenant.count(t),
                "ttft_p99_ms": round(
                    tt[min(len(tt) - 1, int(0.99 * len(tt)))] * 1e3, 1)
                if tt else None}
        sla_viol = sum(h.server.metrics.sla_violations
                       for h in mgr.handles.values() if h.server is not None)
        wall = time.perf_counter() - t_start
        return {"metric": "fleet_elastic_tok_s", "value": round(tok_up, 2),
                "unit": "tok/s", "vs_baseline": None, "seed": SEED,
                "requests": len(resps), "shed": shed,
                "tokens_per_request": mnt, "requeues": router.requeues,
                "lost_handles": len(lost), "failed_handles": len(failed),
                "tok_s_one_replica": round(tok_down, 2),
                "tok_s_post_join": round(tok_up, 2),
                "scale_out_replica": 2, "scale_in_replica": scale_in_rid,
                "zero_probe_join": rep2.zero_probe_join(),
                "joiner_warm_s": round(rep2.warm_s, 3),
                "p99_ttft_s": round(p99_ttft, 3),
                "per_tenant": per_tenant, "sla_violations": sla_viol,
                "doctor_verdict": report["verdict"],
                "wall_s": round(wall, 2),
                "device": jax.devices()[0].platform}
    finally:
        configure_chaos(None)
        if mgr is not None:
            mgr.close()
        _shutil.rmtree(work, ignore_errors=True)


def model_family_bench():
    """Rung mf (model-family AutoTP ladder, deepspeed_tpu/sharding/): the
    PR 18 acceptance as a measured rung — each built-in rule pack's family
    (llama / mistral / gpt_neox / mixtral) goes from a raw HF-layout
    checkpoint through ``autotp_initialize`` to a tp=2 × ZeRO-3 engine with
    ZERO model-specific code, trains three steps, and its compiled train
    step is audited against the planner's plan records. The headline value
    is the number of families that audit clean (zero errors AND zero
    unplanned gather-class collectives) — deterministic, gated tight: a
    rules/packs/planner-registration regression that lets GSPMD slip an
    unplanned gather into ANY family must fail CI, not just slow it down.
    Per-family train-step wall time and finding counts ride along."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.analysis import AuditOptions, audit_step
    from deepspeed_tpu.sharding.audit_entry import FAMILIES, family_engine

    per_family = {}
    clean = 0
    for fam in FAMILIES:
        engine, b = family_engine(fam, tp=2, zero_stage=3)
        step_rng = jax.random.PRNGKey(0)
        losses = [float(engine.train_batch(b)) for _ in range(3)]
        best_step = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(engine.train_batch(b))
            best_step = min(best_step, time.perf_counter() - t0)
        traced = engine._train_step.trace(engine.state, b, step_rng)
        exe = traced.lower().compile()
        ledger = dist.get_comms_logger()
        axis_sizes = {str(k): int(v)
                      for k, v in dict(engine.topo.mesh.shape).items()}
        rep = audit_step(traced, compiled=exe, label=f"autotp-{fam}",
                         options=AuditOptions(), axis_sizes=axis_sizes,
                         plan_records=ledger.plan_records, ledger=ledger)
        counts = rep.counts()
        unplanned = int(rep.context.get("unplanned_collectives") or 0)
        ok = counts.get("error", 0) == 0 and unplanned == 0
        clean += int(ok)
        per_family[fam] = {
            "clean": ok, "unplanned": unplanned,
            "errors": counts.get("error", 0),
            "warnings": counts.get("warning", 0),
            "hlo_collectives": rep.context.get("hlo_collectives"),
            "train_step_ms": round(best_step * 1e3, 2),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "loss_decreased": losses[-1] < losses[0]}
    return {"metric": "autotp_families_clean", "value": clean,
            "unit": f"families/{len(FAMILIES)}", "vs_baseline": None,
            "families": per_family,
            "device": jax.devices()[0].platform}


def integrity_bench():
    """Rung si (silent-corruption integrity tier, runtime/resilience/
    integrity.py + control/policy.py's integrity rule): two halves.

    (1) Armed fingerprint overhead — the cost the tier rides on EVERY
    step when enabled: the in-jit digest issue, the pre-step retention
    copy on fingerprint steps, and the one-step-delayed 8-word harvest.
    Measured as best-of-3 mean step time armed (world=1: the compute-side
    contract; the store publish is a per-interval KB-sized JSON write off
    the hot loop) vs integrity-off on the same model, and ASSERTED under
    1% — the tier's whole design premise is that detection is cheap
    enough to leave on.

    (2) The gated e2e SDC drill, both chaos classes: three in-process
    engines share a fingerprint store; a bit flip lands on rank 1
    (sticky from step 7 / one-shot transient AT fingerprint step 8). The
    invariants are asserted in-process — detection at the next
    fingerprint step, shadow-replay verdict correct, quarantine for
    sticky only, rollback to a verified snapshot, and final loss BITWISE
    equal to a fault-free reference — so any violation errors the rung
    and gates. The headline is the number of SDC classes fully healed."""
    import shutil as _shutil
    import tempfile

    import deepspeed_tpu as ds

    def make_params(hidden, nlayers=3, seed=0):
        rng = np.random.default_rng(seed)
        p = {}
        for i in range(nlayers):
            p[f"layer_{i}"] = {
                "w": jnp.asarray(rng.normal(0, 0.05, size=(hidden, hidden)),
                                 jnp.float32),
                "b": jnp.zeros((hidden,), jnp.float32)}
        p["head"] = {"w": jnp.asarray(rng.normal(0, 0.05, size=(hidden, 1)),
                                      jnp.float32)}
        return p

    def mlp_loss(params, batch):
        x, y = batch["x"], batch["y"]
        h = x
        n = len([k for k in params if k.startswith("layer_")])
        for i in range(n):
            h = jnp.tanh(h @ params[f"layer_{i}"]["w"]
                         + params[f"layer_{i}"]["b"])
        pred = h @ params["head"]["w"]
        return jnp.mean((pred - y.astype(pred.dtype)) ** 2)

    mlp_loss._sharding_native = True

    def mk_batches(n, hidden, bs, seed=0):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=(hidden, 1)).astype(np.float32)
        out = []
        for _ in range(n):
            x = rng.normal(size=(bs, hidden)).astype(np.float32)
            y = x @ w + 0.01 * rng.normal(size=(bs, 1)).astype(np.float32)
            out.append({"x": jnp.asarray(x), "y": jnp.asarray(y)})
        return out

    work = tempfile.mkdtemp(prefix="dstpu_si_")
    try:
        # -- (1) armed overhead on a step big enough to be the signal ----
        HIDDEN, BATCH, FP_EVERY, MEASURE = 512, 128, 32, 64

        def build(name, armed):
            cfg = {"train_micro_batch_size_per_gpu": BATCH,
                   "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                   "steps_per_print": 10**9, "seed": 11,
                   "resilience": {"enabled": True,
                                  "snapshot_dir": os.path.join(work, name),
                                  "snapshot_interval": 10**9,
                                  "async_snapshot": False}}
            if armed:
                cfg["resilience"]["integrity"] = {
                    "enabled": True, "interval_steps": FP_EVERY, "world": 1,
                    "dir": os.path.join(work, name, "fp")}
            e, *_ = ds.initialize(model=mlp_loss,
                                  model_parameters=make_params(HIDDEN),
                                  config=cfg)
            return e

        bs = mk_batches(4, HIDDEN, BATCH, seed=3)

        def run_arm(e):
            for i in range(8):      # warm: train-step + fingerprint compiles
                e.train_batch(bs[i % 4])
            jax.block_until_ready(e.state)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(MEASURE):
                    e.train_batch(bs[i % 4])
                jax.block_until_ready(e.state)
                best = min(best, time.perf_counter() - t0)
            return best / MEASURE

        off_s = run_arm(build("off", False))
        armed_eng = build("armed", True)
        armed_s = run_arm(armed_eng)
        overhead_pct = (armed_s - off_s) / off_s * 100.0
        assert overhead_pct < 1.0, (
            f"armed integrity overhead {overhead_pct:.2f}% of step time "
            f"breaches the <1% design budget")
        # raw digest latency (full issue+fetch round trip, no amortization)
        fp_fn = armed_eng.resilience.integrity._fp_fn
        np.asarray(fp_fn(armed_eng.state))
        t0 = time.perf_counter()
        np.asarray(fp_fn(armed_eng.state))
        fp_ms = (time.perf_counter() - t0) * 1e3

        # -- (2) the gated drill, one pass per SDC class -----------------
        D_HIDDEN, D_BATCH, D_STEPS, SNAP_IVL, FP_IVL = 32, 4, 14, 4, 2

        def drill_engine(kind, rank, faults):
            cfg = {"train_micro_batch_size_per_gpu": D_BATCH,
                   "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
                   "steps_per_print": 10**9, "seed": 7,
                   "control": {"enabled": True,
                               "supervisor": {"interval_steps": 1,
                                              "straggler_replan": False,
                                              "memory_guard": False,
                                              "rollback_degrade": False},
                               "guard": {"trigger_streak": 1,
                                         "clear_streak": 1,
                                         "cooldown_s": 0.0, "budget": 100}},
                   "resilience": {
                       "enabled": True,
                       "snapshot_dir": os.path.join(
                           work, f"drill-{kind}-snap-{rank}"),
                       "snapshot_interval": SNAP_IVL,
                       "async_snapshot": False,
                       "integrity": {"enabled": True,
                                     "interval_steps": FP_IVL,
                                     "rank": rank, "world": 3,
                                     "dir": os.path.join(work,
                                                         f"drill-{kind}-fp"),
                                     "resolve_timeout_steps": 6}}}
            if faults is not None and rank == 1:
                cfg["resilience"]["faults"] = faults
            e, *_ = ds.initialize(model=mlp_loss,
                                  model_parameters=make_params(D_HIDDEN),
                                  config=cfg)
            return e

        d_batches = mk_batches(D_STEPS + 4, D_HIDDEN, D_BATCH, seed=0)
        ref = drill_engine("ref", 0, None)
        ref.resilience.integrity.cfg.interval_steps = 10**9  # ref: fp off
        ref_losses = {}
        while ref.global_steps < D_STEPS:
            gs = ref.global_steps
            ref_losses[gs + 1] = float(np.asarray(
                ref.train_batch(d_batches[gs])))

        drill = {}
        cases = (("sticky", {"enabled": True, "sdc_sticky_from_step": 7,
                             "sdc_rank": 1}),
                 ("transient", {"enabled": True,
                                "sdc_transient_at_steps": [8],
                                "sdc_rank": 1}))
        for kind, faults in cases:
            engines = [drill_engine(kind, r, faults) for r in range(3)]
            alive = {0, 1, 2}
            finals = {}
            for _ in range(200):
                if not any(engines[r].global_steps < D_STEPS for r in alive):
                    break
                for r in sorted(alive):
                    e = engines[r]
                    if e.global_steps >= D_STEPS:
                        continue
                    gs = e.global_steps
                    loss = float(np.asarray(e.train_batch(d_batches[gs])))
                    if gs + 1 == D_STEPS:
                        finals[r] = loss
                for r in sorted(alive):
                    mon = engines[r].resilience.integrity
                    if mon.quarantined and r in mon.quarantined:
                        alive.discard(r)       # fleet acts on the verdict
            else:
                raise AssertionError(f"{kind} drill did not converge")
            healthy = sorted(alive)
            mon0 = engines[healthy[0]].resilience.integrity
            assert mon0.divergences, f"{kind}: divergence never detected"
            first = mon0.divergences[0]
            assert first["step"] == 8 and first["minority"] == [1], first
            led = engines[healthy[0]].control.ledger.snapshot()
            quarantined = any(a["action"] == "sdc_quarantine"
                              and 1 in a["params"]["ranks"] for a in led)
            assert quarantined == (kind == "sticky"), (
                f"{kind}: quarantine={quarantined}")
            assert any(a["action"] == "integrity_rollback"
                       and a["outcome"] == "ok" for a in led), kind
            bitwise = all(finals[r] == ref_losses[D_STEPS] for r in healthy)
            assert bitwise, (
                f"{kind}: healed losses not bitwise equal to fault-free ref")
            drill[kind] = {"detected_step": first["step"],
                           "verdict": first["verdict"],
                           "quarantined": quarantined,
                           "healthy_ranks": healthy,
                           "bitwise_recovery": bitwise}
        classes = len(drill)
    finally:
        _shutil.rmtree(work, ignore_errors=True)

    return {"metric": "integrity_sdc_classes_healed", "value": classes,
            "unit": "classes/2", "vs_baseline": None,
            "armed_overhead_pct": round(overhead_pct, 3),
            "off_step_ms": round(off_s * 1e3, 3),
            "armed_step_ms": round(armed_s * 1e3, 3),
            "fingerprint_ms": round(fp_ms, 3),
            "fp_interval_steps": FP_EVERY,
            "drill": drill,
            "device": jax.devices()[0].platform}


RUNGS = {"1": rung1_simple_zero0, "2": rung2_gpt2_zero1,
         "3b": rung3b_big_model,
         "4": rung4_pipeline_bubble, "5": rung5_moe_ulysses,
         "cm": collective_matmul_bench, "qx": quantized_collectives_bench,
         "plan": planner_bench, "rz": resilience_bench,
         "wd": watchdog_bench, "fl": fused_hotpath_bench,
         "sv": serving_bench, "sv2": serving_prefix_reuse_bench,
         "pd": paged_decode_bench,
         "ds": dcn_hierarchical_bench, "t3": fused_phase_bench,
         "cp": program_compiler_bench,
         "ob": telemetry_bench, "mem": memory_telemetry_bench,
         "sa": static_audit_bench, "at": control_bench,
         "cz": chaos_soak_bench, "mf": model_family_bench,
         "fs": fleet_serving_bench, "si": integrity_bench}


# ---------------------------------------------------------------------------
# ladder self-gating: every rung row is compared against the recorded
# LADDER.json baseline — vs_baseline stops being None, and `--gate` turns
# the comparison into an exit code so BENCH-trajectory reading becomes CI.
# ---------------------------------------------------------------------------

LADDER_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "LADDER.json")

# metric -> (direction, relative tolerance). Direction names which way
# regression lies; tolerance absorbs shared-box timing noise (generous for
# wall-clock metrics — a real regression is 2x, noise is tens of percent)
# and is tight for deterministic byte accounting.
GATE_DEFAULT = ("higher", 0.5)
GATE_SPECS = {
    "watchdog_arm_disarm_us": ("lower", 1.0),
    "telemetry_span_overhead_ns": ("lower", 1.0),
    "collective_ring_overhead_ns": ("lower", 1.0),
    "static_audit_train_ms": ("lower", 1.0),     # host walk: wall-clock noise
    "control_decide_ns": ("lower", 1.0),         # supervisor loop: host cost
    "dcn_hierarchical": ("higher", 0.05),        # ledger bytes: deterministic
    "fused_exposed_fraction": ("lower", 0.05),   # ledger bytes: deterministic
    # menu/searched DCN-exposure ratio: pure cost-model arithmetic over the
    # two programs' phase structure — deterministic, tight gate
    "program_search_dcn_speedup": ("higher", 0.05),
    "llama_zero3_bf16_mfu": ("higher", 0.15),    # the TPU headline: tight
    "paged_decode_step_ms": ("lower", 1.0),      # decode hot path: wall-clock
    # reuse-arm/baseline-arm ratio: both arms share the box so load noise
    # largely cancels, but the arms are wall-clock — keep the default slack
    "serving_prefix_reuse_speedup": ("higher", 0.5),
    "chaos_soak_fault_classes": ("higher", 0.05),  # seeded count: deterministic
    "autotp_families_clean": ("higher", 0.05),  # family count: deterministic
    # fleet post-join tok/s: wall-clock on a shared box, keep the default
    # slack — the rung's REAL gates (zero lost requests, zero-probe join,
    # kill->join tok/s rise, bounded p99 TTFT, doctor naming every event)
    # are in-process asserts, so any violation errors the rung and gates
    "fleet_elastic_tok_s": ("higher", 0.5),
    # SDC classes healed end-to-end: deterministic drill count, and the
    # <1% armed-overhead budget is an in-process assert that errors the
    # rung — wall-clock noise never rides the gated value itself
    "integrity_sdc_classes_healed": ("higher", 0.05),
}


def load_ladder_baseline(path: str = None):
    """``metric -> recorded rung row`` from LADDER.json; empty when the
    baseline file is absent or unreadable (first run records, never gates)."""
    try:
        with open(path or LADDER_PATH) as f:
            rows = json.load(f)
    except (OSError, ValueError):
        return {}
    return {r["metric"]: r for r in rows
            if isinstance(r, dict) and r.get("metric")}


def fill_vs_baseline(rec: dict, baseline: dict) -> dict:
    """Populate ``vs_baseline`` from the LADDER.json row for this metric
    (current/recorded). Rungs that already computed a target-relative value
    (the MFU rows' value/TARGET_MFU) keep it — the gate reads the raw
    values either way."""
    row = baseline.get(rec.get("metric"))
    if (rec.get("vs_baseline") is None and row is not None
            and isinstance(rec.get("value"), (int, float))
            and isinstance(row.get("value"), (int, float)) and row["value"]):
        rec["vs_baseline"] = round(rec["value"] / row["value"], 4)
    return rec


def gate_results(results, baseline, specs: dict = None):
    """Compare rung rows against the recorded baseline; returns the list of
    regression dicts (empty = ladder passes). A rung with no baseline row is
    new and never gates; a rung that ERRORED where the baseline has a value
    is itself a regression (a broken bench must fail CI, not skip it)."""
    specs = GATE_SPECS if specs is None else specs
    # a crashed rung subprocess yields {"metric": "rung<id>", "value": None}
    # — no metric-name match, but the baseline rows carry their rung id, so
    # the crash still gates against the row it failed to reproduce
    by_rung = {row.get("rung"): row for row in baseline.values()
               if row.get("rung") is not None}
    failures = []
    for rec in results:
        metric = rec.get("metric")
        row = baseline.get(metric)
        if (row is None and rec.get("value") is None
                and rec.get("rung") is not None):
            # ERROR rows only: a successful rung whose metric name merely
            # differs from the baseline's (rung 3's TPU-vs-CPU variants) is
            # a different measurement, not a crash to gate by rung id
            row = by_rung.get(rec.get("rung"))
            if row is not None:
                metric = row.get("metric")
        if row is None or not isinstance(row.get("value"), (int, float)):
            continue
        direction, tol = specs.get(metric, GATE_DEFAULT)
        bval, val = row["value"], rec.get("value")
        if not isinstance(val, (int, float)):
            failures.append({"metric": metric, "baseline": bval,
                             "value": None,
                             "why": rec.get("error", "no value")})
            continue
        bad = (val < bval * (1.0 - tol) if direction == "higher"
               else val > bval * (1.0 + tol))
        if bad:
            failures.append({
                "metric": metric, "baseline": bval, "value": val,
                "direction": direction, "tolerance": tol,
                "why": (f"{val:g} vs baseline {bval:g} "
                        f"({'below' if direction == 'higher' else 'above'} "
                        f"the {tol:.0%} gate)")})
    return failures


def gate_report(failures, n_checked: int) -> str:
    if not failures:
        return f"GATE PASS: {n_checked} rung(s) within tolerance of LADDER.json"
    lines = [f"GATE FAIL: {len(failures)} regression(s) vs LADDER.json"]
    for f in failures:
        lines.append(f"  {f['metric']}: {f['why']}")
    return "\n".join(lines)


def _with_ledger(fn):
    """Run one rung with the comms ledger enabled and attach the per-op
    totals (logical and wire bytes per collective) to its JSON row, so
    LADDER.json carries the communication profile alongside the timing."""
    import deepspeed_tpu.comm as dist

    logger = dist.get_comms_logger()
    logger.configure(enabled=True, prof_all=True)
    logger.reset()
    try:
        rec = fn()
    finally:
        totals = logger.totals()
        logger.configure(enabled=False)
        logger.reset()
    if totals:
        rec["comms_ledger"] = totals
    return rec


def run_ladder(gate: bool = False):
    """Spawn one subprocess per rung (each needs its own XLA device config);
    print each rung's JSON line and write LADDER.json. With ``gate`` the
    recorded LADDER.json is the BASELINE: rows are compared instead of
    rewritten and the return code is nonzero on any regression."""
    import subprocess
    import sys

    from deepspeed_tpu.utils.health import accelerator_device_count

    baseline = load_ladder_baseline()
    cpu8 = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    cpu32 = {"JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=32"}
    cpu1 = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    chip = {}  # the environment's own platform: a rung that finds no
               # device there fails, it is never re-run on the CPU
    # one process per chip: this parent never initializes a backend (it
    # would hold the chip and starve the rung children), so the device
    # count comes from a child that exits before the first rung starts
    n_dev = accelerator_device_count()
    if n_dev < 1:
        raise SystemExit("ladder: the default jax backend reports no device")
    multichip = n_dev > 1
    plan = [("1", cpu1), ("2", chip), ("3", chip), ("4", cpu8), ("5", cpu8),
            ("cm", {} if multichip else cpu8),
            ("qx", {} if multichip else cpu8),
            ("plan", {} if multichip else cpu8),
            ("rz", chip), ("wd", cpu1), ("fl", chip), ("sv", chip),
            # sv2 serves the same prefix-heavy trace with the prefix cache
            # + spec decode off then on; the row is the speedup ratio
            ("sv2", chip),
            # pd compares the paged decode kernel against the einsum
            # reference (interpret-mode pallas on CPU; real kernel on TPU)
            ("pd", chip),
            # ds simulates the DCN split (dcn_axes override) — the virtual
            # CPU mesh IS the measurement substrate, even beside a real chip
            ("ds", cpu8),
            # t3 gates the fused-phase programs on the same simulated DCN
            # split: exposed-collective fraction from the ledger exposure
            # buckets, fused vs the sequenced PR 8 program at equal wire
            ("t3", cpu8),
            # cp searches the 3-axis ici x ici x dcn program space the fixed
            # menu was never written for (32 virtual devices: dp_outer=8
            # forced DCN, ep=2, tp=2) — menu-vs-searched DCN exposure
            ("cp", cpu32), ("ob", cpu1),
            # mem measures the recorder/gauge costs (host side), with real
            # HBM numbers beside them where the platform reports them
            ("mem", chip),
            # sa times the static auditor itself (host-side HLO/jaxpr
            # walks — device-independent, one CPU process is the substrate)
            ("sa", cpu1),
            # at times the control plane: autotune probes are real engine
            # builds (8-dev mesh matches the test/drill substrate), the
            # decision loop is pure host work
            ("at", cpu8),
            # cz soaks the chaos engine: seeded full-stack fault schedule
            # over serving + training drills with the survival invariants
            # asserted in-process (one CPU device is the substrate)
            ("cz", cpu1),
            # fs soaks the fleet tier: chaos replica kill mid-burst, SLA
            # scale-out through the supervisor (zero-probe warm join),
            # flap-guarded scale-in — elastic-serving invariants asserted
            # in-process (one CPU device is the substrate)
            ("fs", cpu1),
            # si arms the integrity tier's cross-rank fingerprints: armed
            # step overhead vs off (asserted <1%), then the gated SDC
            # drill — sticky and transient bit flips detected, classified
            # by shadow replay, quarantined/rolled back to bitwise
            # recovery (one CPU device is the substrate)
            ("si", cpu1),
            # mf auto-shards every built-in rule-pack family (llama,
            # mistral, gpt_neox, mixtral) at tp=2 x ZeRO-3 via
            # autotp_initialize and audits each compiled step to zero
            # unplanned gather-class collectives
            ("mf", cpu8)]
    results = []
    for rung, env_over in plan:
        env = dict(os.environ)
        env.update(env_over)
        argv = [sys.executable, os.path.abspath(__file__)]
        argv += ["--rung", rung] if rung != "3" else []
        try:
            out = subprocess.run(argv, env=env, capture_output=True, text=True,
                                 timeout=2400)
            lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
            if not lines:
                raise RuntimeError(
                    f"rc={out.returncode}; stderr tail: "
                    + " | ".join(out.stderr.splitlines()[-4:]))
            rec = json.loads(lines[-1])
        except Exception as e:
            rec = {"metric": f"rung{rung}", "value": None, "unit": "error",
                   "vs_baseline": None, "error": str(e)[:400]}
        # numeric ladder rungs keep their integer id; named rungs (cm/qx/
        # plan) keep the name — int("cm") used to throw and kill the ladder
        rec["rung"] = int(rung) if rung.isdigit() else rung
        fill_vs_baseline(rec, baseline)
        print(json.dumps(rec))
        results.append(rec)
    if gate:
        failures = gate_results(results, baseline)
        print(gate_report(failures, len(results)))
        return 1 if failures else 0
    with open(LADDER_PATH, "w") as f:
        json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--ladder", action="store_true",
                    help="run all BASELINE.md ladder rungs")
    ap.add_argument("--rung", choices=sorted(RUNGS),
                    help="run one ladder rung in-process")
    ap.add_argument("--gate", action="store_true",
                    help="compare against the recorded LADDER.json baseline "
                         "and exit nonzero on regression (with --ladder runs "
                         "the rungs; with --results gates a recorded file)")
    ap.add_argument("--results", default=None,
                    help="with --gate: gate this previously-recorded results "
                         "JSON instead of re-running the rungs")
    ap.add_argument("--baseline", default=None,
                    help="with --gate: baseline file (default LADDER.json)")
    args = ap.parse_args()
    if args.gate and args.results:
        # CI fast path: gate recorded rows without touching any backend
        with open(args.results) as f:
            results = json.load(f)
        baseline = load_ladder_baseline(args.baseline)
        for rec in results:
            fill_vs_baseline(rec, baseline)
        failures = gate_results(results, baseline)
        print(gate_report(failures, len(results)))
        raise SystemExit(1 if failures else 0)
    if args.ladder or args.gate:
        raise SystemExit(run_ladder(gate=args.gate))
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.rung:
        # rungs whose substrate IS the virtual CPU mesh (relative numbers
        # across a mesh, simulated DCN splits, host-side control loops)
        # select it here unless the caller preset a device count; every
        # other rung runs on the platform the environment names and fails
        # if it cannot be reached
        mesh_n = {"4": 8, "5": 8, "ds": 8, "t3": 8, "at": 8, "mf": 8,
                  "cp": 32}.get(args.rung)
        if mesh_n and ("--xla_force_host_platform_device_count"
                       not in os.environ.get("XLA_FLAGS", "")):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={mesh_n}")
            os.environ["JAX_PLATFORMS"] = "cpu"
            jax.config.update("jax_platforms", "cpu")
        rec = _with_ledger(RUNGS[args.rung])
        fill_vs_baseline(rec, load_ladder_baseline())
        print(json.dumps(rec))
    else:
        main()
