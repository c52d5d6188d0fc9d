"""``chip_smoke.py`` off the chip: its phases at a tiny size on the CPU mesh,
and its refusal to pass for a chip that is not there."""

import importlib.util
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gpt2(**kw):
    from deepspeed_tpu.models.transformer import gpt2_config

    return gpt2_config("small", num_layers=1, hidden_size=64,
                       intermediate_size=128, num_heads=4, vocab_size=256,
                       max_seq_len=64, dtype=jnp.float32, **kw)


def _train(smoke, watch):
    out = smoke.train_phase(watch, seed=0, cfg=_gpt2(), batch=8, seq=64,
                            steps=2)
    assert out["compiled"] == 1 and out["attn_impl"] == "xla"


def _serve(smoke, watch):
    from deepspeed_tpu.models.transformer import llama_config

    cfg = llama_config("tiny", num_layers=2, hidden_size=64,
                       intermediate_size=128, num_heads=4, num_kv_heads=2,
                       vocab_size=256, max_seq_len=128, dtype=jnp.float32)
    out = smoke.serve_phase(watch, seed=0, cfg=cfg, prompt_lens=(5, 12, 21),
                            max_new=10, kv_blocks=24, kv_block_size=8,
                            max_chunk=16, decode_chunk=4, logit_tol=1e-3)
    assert out["tokens_out"] == 30 and out["decode_attn_impl"] == "einsum"
    # fp32 on one backend: the served tokens ARE the reference's argmax
    assert out["reference"]["argmax_agree"] == out["reference"]["tokens_checked"]


def _sharded(smoke, watch):
    out = smoke.sharded_train_phase(watch, seed=0, cfg=_gpt2(), batch=8,
                                    seq=64, steps=2, loss_tol=1e-3)
    assert out["sharded"]["devices"] == 4 and out["sharded"]["tp"] == 2
    assert out["single"]["devices"] == 1


@pytest.mark.parametrize("phase", [_train, _serve, _sharded],
                         ids=["train", "serve", "zero3_tp2_vs_one_device"])
def test_phase_passes_tiny_on_cpu_mesh(smoke, phase, capsys):
    with smoke.CompileWatch() as watch:
        phase(smoke, watch)
    # every line the phase printed is one JSON object, and none is a verdict
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(isinstance(json.loads(l), dict) for l in lines)
    assert not any("ok" in json.loads(l) for l in lines)


def test_phase_fails_when_the_named_kernel_is_not_in_the_program(smoke):
    """``attn_impl: flash`` on the CPU runs the kernel in the interpreter:
    the resolved implementation names a kernel the compiled program does not
    hold, which is exactly what a silent demotion on the chip would look
    like. The phase must raise, not report."""
    with smoke.CompileWatch() as watch:
        with pytest.raises(smoke.SmokeFailure, match="flash_attention_fwd"):
            smoke.run_trainer(watch, seed=0, cfg=_gpt2(attn_impl="flash"),
                              batch=8, seq=32, steps=1, zero_stage=1)


def test_script_without_a_chip_exits_nonzero_and_prints_no_verdict():
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=120, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "TPU" in r.stderr
