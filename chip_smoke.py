#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two products once, through the entry points a user calls, on one
TPU chip in one process:

* trainer: ``ds.initialize`` -> ``engine.train_batch`` on GPT-2-small (bf16,
  published widths, full depth, ZeRO-1 + fused Adam) for a few steps on one
  repeated batch;
* server: ``LLMServer`` over ``InferenceEngineV2`` on the TinyLlama-1.1B
  shape (bf16, full depth) with a KV page pool sized in gigabytes, a few
  greedy requests through ``submit``, two of them checked against
  ``TransformerLM.apply`` through the XLA attention on the same chip.

``--chips 4`` runs instead, and alone, the sharded trainer (ZeRO-3 x TP 2 on
a four-chip host) and its one-chip comparison.

Weights are random from ``--seed``; nothing is downloaded. Every line of
stdout is one JSON object: versions, what each phase resolved and compiled,
what it checked. It prints no speed: this is not an instrument. Any failed
check or raised phase ends the run with a non-zero exit code, and so does a
machine where JAX finds no TPU. Only a clean run ends with
``{"ok": true, "device": {...}}``.

The phases are plain functions of their sizes, so ``tests/unit/
test_chip_smoke.py`` runs them tiny on the CPU; ``main`` is the only place
where a missing chip is fatal.
"""

import argparse
import collections
import dataclasses
import gc
import json
import math
import os
import re
import sys
import tempfile

import numpy as np

# Pallas kernel names (the ``name=`` of each ``pallas_call`` in ops/pallas/)
# whose presence in a lowered program proves that implementation compiled.
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkdv")
FUSED_LOSS_KERNELS = ("fused_vocab_nll_fwd", "fused_vocab_nll_bwd_dh",
                      "fused_vocab_nll_bwd_dk")
PREFILL_KERNEL = "paged_attention"
DECODE_KERNEL = "paged_flash_decode"

# A served greedy token must be the reference's argmax up to this margin in
# its logit. bf16 keeps 8 significant bits; across 22 layers the two
# implementations' logits (|logit| of a few units) differ by a few 1e-2:
# the worst of 128 checked tokens trailed by 0.02 on the chip (PR 21).
LOGIT_TOL_BF16 = 0.1
# Sharded and one-chip bf16 training losses may differ by reduction order.
# The engine reports the loss in bf16, in steps of 0.0625 near 10: two such
# steps, absolute, on every step (they were equal on the chip, PR 21).
SHARDED_LOSS_TOL_BF16 = 0.125


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileWatch:
    """What JAX compiled while this context is open: seconds in the backend
    compiler (or loading from the persistent cache), cache hits and misses
    (``jax.monitoring`` events), and every lowered module, which JAX dumps to
    a temporary directory (``jax_dump_ir_to``) so the programs that actually
    ran can be searched for their kernels."""

    def __init__(self):
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._active = False
        self._reported = set()

    def __enter__(self):
        import jax

        self._tmp = tempfile.TemporaryDirectory(prefix="chip_smoke_ir_")
        self._prev_dump = jax.config.read("jax_dump_ir_to")
        jax.config.update("jax_dump_ir_to", self._tmp.name)
        # listeners cannot be unregistered through the public API: they go
        # quiet once the context closes
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self._active = True
        return self

    def __exit__(self, *exc):
        import jax

        self._active = False
        jax.config.update("jax_dump_ir_to", self._prev_dump)
        self._tmp.cleanup()

    def _on_event(self, event, **_):
        if self._active and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif self._active and event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if self._active and event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += duration

    def take(self) -> dict:
        """Set-up cost since the last call (and reset)."""
        out = {"compile_seconds_setup": round(self.compile_seconds, 1),
               "compile_cache_hits": self.cache_hits,
               "compile_cache_misses": self.cache_misses}
        self.compile_seconds, self.cache_hits, self.cache_misses = 0.0, 0, 0
        return out

    def program(self, name: str) -> dict:
        """Kernel census of the modules named ``jit_<name>`` lowered since
        the last call for that name: how many were compiled, how many
        ``tpu_custom_call`` s they hold, and which kernels those are."""
        n_programs, n_calls, kernels = 0, 0, collections.Counter()
        for fname in sorted(os.listdir(self._tmp.name)):
            if f"_jit_{name}_" not in fname or fname in self._reported:
                continue
            self._reported.add(fname)
            with open(os.path.join(self._tmp.name, fname)) as f:
                text = f.read()
            n_programs += 1
            n_calls += text.count("@tpu_custom_call")
            kernels.update(re.findall(r'kernel_name = "(\w+)"', text))
        return {"program": f"jit_{name}", "compiled": n_programs,
                "tpu_custom_calls": n_calls, "kernels": dict(kernels)}


def check_kernels(census: dict, expected) -> None:
    """The lowered program holds exactly the kernels its resolved
    implementation names: none missing (a silent demotion) and none besides
    (a resolution this script reports wrongly)."""
    check(census["compiled"] > 0,
          f"{census['program']} was never compiled: that path did not run")
    found = set(census["kernels"])
    check(found == set(expected),
          f"{census['program']}: the resolved implementation names kernels "
          f"{sorted(expected)}, the compiled program holds {sorted(found)}")


def count_params(tree) -> int:
    import jax

    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree))


def memory_stats(device) -> dict:
    """``bytes_in_use`` / ``peak_bytes_in_use`` as the device reports them
    (None where the backend keeps no such statistics, as the CPU does)."""
    stats = device.memory_stats() or {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def run_trainer(watch: CompileWatch, *, seed: int, cfg, batch: int, seq: int,
                steps: int, zero_stage: int, tp: int = 1, devices=None,
                label: str = "train") -> dict:
    """``ds.initialize`` -> ``steps`` x ``engine.train_batch`` on one repeated
    batch over ``devices`` (default: all). Returns the losses, what was
    resolved and compiled, and per-device memory while the state is alive."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                                  make_loss_fn, param_specs,
                                                  resolve_attn_impl)
    from deepspeed_tpu.parallel import Topology, TopologySpec
    from deepspeed_tpu.sequence.cross_entropy import resolve_loss_impl

    model = TransformerLM(cfg)

    def init():  # a closure: every leaf materializes straight into its shard
        return init_params(model, seed=seed, batch=1, seq=seq)

    topo = Topology(TopologySpec(tp=tp), devices=devices)
    dp = topo.n_devices // tp
    engine, *_ = ds.initialize(
        model=make_loss_fn(model), model_parameters=init, topology=topo,
        param_specs=param_specs(jax.eval_shape(init)) if tp > 1 else None,
        config={"train_micro_batch_size_per_gpu": batch // dp,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": zero_stage},
                "bf16": {"enabled": jnp.dtype(cfg.dtype) == jnp.bfloat16},
                "steps_per_print": 10**9})
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    data = {"tokens": tokens}
    losses = [float(engine.train_batch(data)) for _ in range(steps)]

    attn_impl = resolve_attn_impl(cfg.attn_impl, seq)
    # make_loss_fn takes the fused (headless) loss only at tp == 1
    loss_impl = (resolve_loss_impl(cfg.loss_impl, cfg.vocab_size)
                 if tp == 1 else "xla")
    census = watch.program("train_step")
    used = list(topo.mesh.devices.flat)
    out = {"phase": label, "devices": len(used), "zero_stage": zero_stage,
           "tp": tp, "dp": dp, "batch": batch, "seq": seq,
           "n_params": count_params(engine.state.params),
           "attn_impl": attn_impl, "loss_impl": loss_impl, **census,
           "losses": [round(l, 4) for l in losses],
           "memory": [memory_stats(d) for d in used], **watch.take()}
    emit(**out)  # before the checks: a failed run still says what it saw
    check_kernels(census,
                  (FLASH_KERNELS if attn_impl == "flash" else ())
                  + (FUSED_LOSS_KERNELS if loss_impl == "fused" else ()))
    check(census["compiled"] == 1,
          f"jit_train_step was compiled {census['compiled']} times for one "
          f"batch shape: its input types changed between calls")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    return out


def train_phase(watch: CompileWatch, *, seed: int, cfg, batch: int, seq: int,
                steps: int) -> dict:
    """The one-chip trainer: ZeRO-1 + fused Adam. The first loss of a random
    model is near ln(vocab), and a few steps on one batch bring it down."""
    out = run_trainer(watch, seed=seed, cfg=cfg, batch=batch, seq=seq,
                      steps=steps, zero_stage=1)
    losses, uniform = out["losses"], math.log(cfg.vocab_size)
    check(abs(losses[0] - uniform) < 1.0,
          f"first loss {losses[0]} is not near ln(vocab) = {uniform:.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    return out


def sharded_train_phase(watch: CompileWatch, *, seed: int, cfg, batch: int,
                        seq: int, steps: int, loss_tol: float) -> dict:
    """ZeRO-3 x TP 2 over four devices, then the same seed and batch on a
    one-device mesh of the first: the losses agree step by step, and while
    the sharded state is alive it is spread over all four devices."""
    import jax

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"needs 4 devices, found {len(jax.devices())}")
    kw = dict(seed=seed, cfg=cfg, batch=batch, seq=seq, steps=steps,
              zero_stage=3)
    sharded = run_trainer(watch, tp=2, devices=devices,
                          label="train_zero3_tp2", **kw)
    gc.collect()  # the sharded engine's buffers go before device 0 is read
    single = run_trainer(watch, devices=devices[:1],
                         label="train_one_device", **kw)
    diffs = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    in_use = [m["bytes_in_use"] for m in sharded["memory"]]
    whole = single["memory"][0]["bytes_in_use"]
    emit(phase="train_zero3_tp2_vs_one_device", loss_tol=loss_tol,
         max_loss_diff=round(max(diffs), 5), sharded_bytes_in_use=in_use,
         one_device_bytes_in_use=whole)
    check(max(diffs) <= loss_tol,
          f"sharded and one-device losses differ by {max(diffs)}: "
          f"{sharded['losses']} vs {single['losses']}")
    check(sharded["losses"][-1] < sharded["losses"][0],
          f"sharded loss did not fall: {sharded['losses']}")
    have_stats = whole is not None and None not in in_use
    check(have_stats or devices[0].platform != "tpu",
          "the chip reported no memory statistics")
    if have_stats:  # the CPU backend keeps none
        check(max(in_use) <= 1.25 * min(in_use),
              f"sharded state is not spread evenly: {in_use}")
        check(max(in_use) <= 0.6 * whole,
              f"a device of the sharded run holds {max(in_use)} bytes, the "
              f"one-device run {whole}: the state is not sharded")
    return {"sharded": sharded, "single": single}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def serve_phase(watch: CompileWatch, *, seed: int, cfg, prompt_lens,
                max_new: int, kv_blocks: int, kv_block_size: int,
                max_chunk: int, decode_chunk: int, logit_tol: float,
                timeout_s: float = 900.0) -> dict:
    """``LLMServer`` over ``InferenceEngineV2``: one greedy request per entry
    of ``prompt_lens``, each for ``max_new`` tokens. The shortest and the
    longest request are then held against the full forward of
    ``TransformerLM.apply`` through the XLA attention, teacher-forced on the
    served tokens: each served token must be the reference's argmax to
    within ``logit_tol`` in its logit."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import TransformerLM, init_params
    from deepspeed_tpu.serving import FINISH_LENGTH, LLMServer, Request

    model = TransformerLM(cfg)
    dtype = jnp.dtype(cfg.dtype)
    # cast inside the jit: the fp32 initial values never stay resident
    params = jax.jit(lambda: jax.tree.map(
        lambda p: p.astype(dtype),
        init_params(model, seed=seed, batch=1, seq=8)))()
    n = len(prompt_lens)
    longest = max(prompt_lens) + max_new
    engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        token_budget=n * max_chunk, max_ragged_sequence_count=n,
        max_chunk_size=max_chunk, num_kv_blocks=kv_blocks,
        kv_block_size=kv_block_size,
        max_blocks_per_seq=-(-longest // kv_block_size), dtype=dtype.name))
    del params
    kv_k, kv_v = engine.kv.pool_args()
    emit(phase="serve", event="engine_built",
         n_params=count_params(engine.params),
         attn_impl=engine.attn_impl,
         decode_attn_impl=engine.decode_attn_impl,
         decode_attn_source=engine.decode_attn_source,
         kv_pool_bytes=int(kv_k.nbytes + kv_v.nbytes),
         kv_pool_shape=list(kv_k.shape), kv_pool_dtype=str(kv_k.dtype),
         kv_pool_tokens=kv_blocks * kv_block_size,
         memory=memory_stats(jax.devices()[0]))

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, m).astype(np.int32)
               for m in prompt_lens]
    server = LLMServer(engine, fused_decode_chunk=decode_chunk)
    server.start()
    try:
        resps = [server.submit(Request(p, max_new_tokens=max_new), block=True)
                 for p in prompts]
        try:
            outs = [r.result(timeout_s) for r in resps]
        except RuntimeError as e:  # the engine thread's own error says why
            raise (server.error or e)
    finally:
        server.close()
    check(server.error is None, f"engine thread died: {server.error!r}")
    for m, r, toks in zip(prompt_lens, resps, outs):
        check(len(toks) == max_new and r.finish_reason == FINISH_LENGTH,
              f"request with a {m}-token prompt returned {len(toks)} tokens "
              f"({r.finish_reason}), expected {max_new}")
        check(toks.min() >= 0 and toks.max() < cfg.vocab_size,
              f"token outside the vocabulary: {toks}")

    prefill = watch.program("ragged_step")
    decode = watch.program("decode_loop")
    setup = watch.take()

    # reference: full forward through the XLA attention on the same weights
    picks = sorted({int(np.argmin(prompt_lens)), int(np.argmax(prompt_lens))})
    width = max(prompt_lens[i] for i in picks) + max_new - 1
    toks = np.zeros((len(picks), width), np.int32)   # right-padded: causal
    pos = np.zeros((len(picks), max_new), np.int32)
    for row, i in enumerate(picks):
        seq_in = np.concatenate([prompts[i], outs[i][:-1]])
        toks[row, :len(seq_in)] = seq_in
        pos[row] = prompt_lens[i] - 1 + np.arange(max_new)
    served = np.stack([outs[i] for i in picks])
    ref_model = TransformerLM(dataclasses.replace(cfg, attn_impl="xla"))

    def margins(p, toks, pos, served):
        logits = ref_model.apply({"params": p}, toks).astype(jnp.float32)
        sel = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        got = jnp.take_along_axis(sel, served[:, :, None], axis=2)[..., 0]
        return sel.max(-1) - got, sel.argmax(-1) == served

    margin, exact = jax.jit(margins)(engine.params, toks, pos, served)
    margin, exact = np.asarray(margin), np.asarray(exact)
    snap = server.metrics.snapshot()
    out = {"phase": "serve", "event": "served", "requests": n,
           "completed": snap["completed"], "failed": snap["failed"],
           "prompt_tokens": int(sum(prompt_lens)),
           "tokens_out": int(sum(len(t) for t in outs)),
           "engine_steps": engine.steps,
           "attn_impl": engine.attn_impl,
           "decode_attn_impl": engine.decode_attn_impl,
           "decode_attn_source": engine.decode_attn_source,
           "programs": [prefill, decode],
           "reference": {"requests_checked": [prompt_lens[i] for i in picks],
                         "tokens_checked": int(exact.size),
                         "argmax_agree": int(exact.sum()),
                         "max_logit_margin": round(float(margin.max()), 4),
                         "logit_tol": logit_tol},
           "memory": memory_stats(jax.devices()[0]), **setup}
    emit(**out)  # before the checks: a failed run still says what it saw
    check_kernels(prefill, (PREFILL_KERNEL,)
                  if engine.attn_impl == "pallas" else ())
    check_kernels(decode, (DECODE_KERNEL,)
                  if engine.decode_attn_impl == "pallas" else ())
    check(snap["completed"] == n and snap["failed"] == 0,
          f"{snap['completed']}/{n} requests completed, {snap['failed']} failed")
    check(float(margin.max()) <= logit_tol,
          f"a served token trails the reference argmax by "
          f"{float(margin.max()):.4f} in logit (tolerance {logit_tol}); "
          f"{int(exact.sum())}/{exact.size} tokens are the argmax")
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ZeRO-3 x TP 2 trainer and its "
                         "one-chip comparison (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2

    import jaxlib

    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.transformer import gpt2_config, llama_config
    from deepspeed_tpu.utils.compile_cache import (compile_cache_dir,
                                                   configure_compile_cache)

    configure_compile_cache()
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    accel = get_accelerator()
    emit(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
         device_kind=devices[0].device_kind, device_count=len(devices),
         accelerator=accel.device_platform(),
         compile_cache_dir=compile_cache_dir(),
         compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         seed=args.seed)
    check(accel.device_platform() == "tpu",
          f"get_accelerator() chose {accel.device_platform()!r} on a TPU host")

    gpt2 = gpt2_config("small", dtype=jnp.bfloat16)
    with CompileWatch() as watch:
        if args.chips == 4:
            sharded_train_phase(watch, seed=args.seed, cfg=gpt2, batch=8,
                                seq=1024, steps=4,
                                loss_tol=SHARDED_LOSS_TOL_BF16)
        else:
            train_phase(watch, seed=args.seed, cfg=gpt2, batch=8, seq=1024,
                        steps=6)
            # 2048 pages x 128 tokens of K and V for 22 layers x 4 kv heads
            # x 64: 5.5 GiB beside 2 GiB of weights on a 16 GiB chip
            serve_phase(watch, seed=args.seed,
                        cfg=llama_config("1b", dtype=jnp.bfloat16),
                        prompt_lens=(128, 256, 384, 512, 640, 768, 896, 1024),
                        max_new=64, kv_blocks=2048, kv_block_size=128,
                        max_chunk=128, decode_chunk=16,
                        logit_tol=LOGIT_TOL_BF16)
    emit(ok=True, device={"platform": devices[0].platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
