"""v2 inference (FastGen analogue): continuous ragged batching + fused decode.

Prompts of different lengths stream through SplitFuse-budgeted prefill
chunks, then the whole decode run executes as one dispatch
(``decode_stream``). Decode tokens/s on a chip: not measured on the current
code.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from examples import _bootstrap  # noqa: E402,F401  (JAX platform handling)

import numpy as np

import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models.transformer import (TransformerLM, init_params,
                                              llama_config)


def main():
    cfg = llama_config("7b", num_layers=2, hidden_size=128,
                       intermediate_size=256, num_heads=4, num_kv_heads=2,
                       vocab_size=512, max_seq_len=256, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = init_params(model, batch=1, seq=64)
    engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        token_budget=64, max_ragged_sequence_count=4, max_chunk_size=32,
        num_kv_blocks=64, kv_block_size=16, max_blocks_per_seq=16))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (7, 19, 33, 12)]  # ragged lengths
    engine.put(list(range(len(prompts))), prompts, max_new_tokens=24)

    while any(s.in_prefill for s in engine.state_manager.all()):
        engine.step()                      # SplitFuse prefill chunks
    out = engine.decode_stream(24)         # ONE dispatch for the whole decode
    for uid in sorted(out):
        print(f"seq {uid}: prompt {len(prompts[uid])} toks -> "
              f"{len(out[uid])} generated: {out[uid][:8]}...")


if __name__ == "__main__":
    main()
