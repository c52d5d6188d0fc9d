"""Config-driven decoder transformer covering the reference's model families.

The reference ships per-architecture injection containers
(``module_inject/containers/{gpt2,llama,llama2,...}``) and fused CUDA layers
(``DeepSpeedTransformerLayer``, ``ops/transformer/transformer.py:296``). Here
one flax module family covers GPT-2 (learned positions, LayerNorm, GELU),
Llama/Mistral (RoPE, RMSNorm, SwiGLU, GQA), and Mixtral (MoE blocks), designed
TPU-first:

* matmuls stay large + bf16 (MXU), logits in fp32;
* tensor parallelism is Megatron-style column/row sharding expressed as
  PartitionSpecs (``param_specs``) — XLA inserts the TP collectives;
* sequence parallelism (Ulysses) wraps the attention core with head-scatter /
  seq-gather all-to-alls (``sequence/layer.py``);
* per-layer rematerialization via ``jax.checkpoint`` replaces the reference's
  activation-checkpointing runtime (``runtime/activation_checkpointing``).
"""

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..sharding import sites


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1376
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None          # GQA; None -> = num_heads
    max_seq_len: int = 2048
    # family switches
    norm: str = "rmsnorm"                       # rmsnorm (llama) | layernorm (gpt2)
    norm_bias: bool = True                      # mpt: LayerNorm without bias
    activation: str = "swiglu"                  # swiglu | gelu | relu | quick_gelu (clip)
    position: str = "rope"                      # rope (llama) | learned (gpt2) | alibi (falcon-rw)
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dropout: float = 0.0
    # architecture flags for the HF container zoo (reference
    # module_inject/containers/*): None = follow the norm-type heuristic
    attn_qkv_bias: Optional[bool] = None        # qwen2: True with rmsnorm
    attn_out_bias: Optional[bool] = None
    mlp_bias: Optional[bool] = None
    parallel_residual: bool = False             # falcon / gpt-neox / gpt-j
    parallel_shared_norm: bool = False          # falcon-7b: one norm feeds both
    rotary_pct: float = 1.0                     # gpt-neox partial rotary
    rotary_interleaved: bool = False            # gpt-j rotate-every-two pairs
    pos_offset: int = 0                         # OPT: learned pos ids offset 2
    embed_norm: bool = False                    # bloom word_embeddings_layernorm
    # falcon/bloom add the ALiBi bias BEFORE the 1/sqrt(d) scaling (the
    # slope is effectively scaled); MPT adds it AFTER (raw slope)
    alibi_post_scale: bool = False
    lm_head_bias: bool = False                  # gpt-j / phi biased lm_head
    no_lm_head: bool = False                    # clip text encoder: return hidden states
    vocab_parallel_loss: bool = False           # tp-sharded CE (sequence/cross_entropy.py)
    attn_scale: Optional[float] = None          # gpt-neo trains UNSCALED (1.0)
    # per-layer attention windows (gpt-neo local attention): tuple with one
    # entry per layer, None = global; e.g. (None, 256, None, 256, ...)
    layer_windows: Optional[Any] = None
    # MoE (mixtral / qwen2_moe): replace the MLP every `moe_every` layers
    num_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1
    # which layers are MoE: layer_idx % moe_every == moe_offset. HF
    # qwen2_moe's decoder_sparse_step rule is (i+1) % step == 0, i.e.
    # offset = step - 1; mixtral is every layer (1, 0)
    moe_offset: int = 0
    moe_intermediate_size: Optional[int] = None  # qwen2_moe: expert ffn != dense ffn
    moe_shared_expert_size: int = 0             # qwen2_moe always-on shared expert
    moe_norm_topk: bool = True                  # mixtral renormalizes top-k; qwen2_moe doesn't
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None  # None | 'Jitter' | 'RSample'
    moe_drop_tokens: bool = True                 # False -> static no-drop capacity k*S
    moe_use_rts: bool = True                     # random token selection on overflow
    moe_use_residual: bool = False               # PR-MoE: dense MLP + learned 2-way coef
    # dropless grouped-GEMM experts (ragged_dot); best with ep=1
    moe_dropless: bool = False
    # execution
    dtype: Any = jnp.bfloat16
    remat: bool = False
    remat_policy: Optional[str] = None
    sequence_parallel: bool = False             # SP over the 'sp' axis
    sp_impl: str = "ulysses"                    # ulysses (all-to-all) | ring
    attn_impl: str = "auto"                     # auto | xla | flash (pallas)
    # serving fused-decode attention (inference/v2): the model-level pin the
    # engine's decode resolution honors first (model field > serving config
    # > planner > heuristic — docs/inference.md decode path)
    decode_attn_impl: str = "auto"              # auto | einsum | pallas
    # Pallas fused LM loss (ops/pallas/fused_loss.py): the lm-head matmul +
    # online-softmax + NLL run blockwise so [B, S, V] logits never
    # materialize; 'auto' defers to the training_fastpath fleet knob then
    # the accelerator heuristic (docs/training_fastpath.md)
    loss_impl: str = "auto"                     # auto | xla | fused
    # ring-overlapped vocab-sharded embedding gather + tied lm head
    # (ops/collective_matmul.py): 'auto' lets the collective planner pick
    # ring vs xla per topology; 'ring' forces it where structurally possible
    embed_overlap: str = "auto"                 # auto | xla | ring
    # ring-overlapped collective matmul (ops/collective_matmul.py): run the
    # column/row-parallel linears (and the Ulysses projection exchange) as
    # shard_map rings that hide the tp/sp collective behind the partial
    # matmuls (T3-style). Also switchable fleet-wide via the runtime knob
    # TensorParallelConfig.overlap_collective_matmul; falls back to the
    # declarative GSPMD path when shapes don't chunk evenly over the axis.
    overlap_collective_matmul: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def rotary_dim(self):
        d = int(self.head_dim * self.rotary_pct)
        return d - d % 2  # rope rotates pairs

    @property
    def qkv_bias(self):
        return (self.norm == "layernorm" if self.attn_qkv_bias is None
                else self.attn_qkv_bias)

    @property
    def out_bias(self):
        return (self.norm == "layernorm" if self.attn_out_bias is None
                else self.attn_out_bias)

    @property
    def ffn_bias(self):
        return self.norm == "layernorm" if self.mlp_bias is None else self.mlp_bias


def _norm(cfg, name):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                        use_bias=cfg.norm_bias, name=name)


def rope_table(seq_len: int, head_dim: int, theta: float):
    pos = np.arange(seq_len)
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    angles = np.outer(pos, freqs)
    return jnp.asarray(np.cos(angles)), jnp.asarray(np.sin(angles))


def apply_rope(x, cos, sin, positions=None, interleaved: bool = False):
    """x: [B, S, H, D]. Two pairing conventions (HF container zoo):
    half-split "rotate_half" (llama/neox — pairs are (i, i+rot/2)) and
    ``interleaved`` "rotate_every_two" (gpt-j — pairs are (2i, 2i+1)).
    Partial rotary (gpt-neox ``rotary_pct`` / gpt-j ``rotary_dim``): when the
    table covers fewer dims than D, only the leading ``2 * cos.shape[-1]``
    dims rotate."""
    rot = 2 * cos.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    if positions is None:
        cos_p = cos[None, :x.shape[1], None, :]
        sin_p = sin[None, :x.shape[1], None, :]
    else:
        cos_p = cos[positions][:, :, None, :]
        sin_p = sin[positions][:, :, None, :]
    if interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        r1 = x1 * cos_p - x2 * sin_p
        r2 = x2 * cos_p + x1 * sin_p
        out = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        x1, x2 = jnp.split(x_rot, 2, axis=-1)
        out = jnp.concatenate([x1 * cos_p - x2 * sin_p,
                               x2 * cos_p + x1 * sin_p], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out.astype(x.dtype)


def apply_activation(name: str, x):
    """Non-gated MLP activation by config name — the ONE dispatch shared by
    the flax MLP and the inference-v2 functional forward, so the two stay in
    lockstep per HF family (swiglu is gated and handled by the callers)."""
    if name == "relu":                # opt
        return jax.nn.relu(x)
    if name == "quick_gelu":          # clip: x * sigmoid(1.702 x)
        return x * jax.nn.sigmoid(1.702 * x)
    if name == "gelu_exact":          # mpt: erf gelu, not tanh
        return jax.nn.gelu(x, approximate=False)
    return jax.nn.gelu(x)


def alibi_slopes(num_heads: int, bf16_round: bool = True) -> np.ndarray:
    """ALiBi per-head slopes (Press et al.; matches the HF implementation
    used by falcon/bloom — geometric in 2^(-8/n), extended for non-pow2).
    ``bf16_round``: HF falcon/bloom round the slopes through bfloat16; MPT
    computes them in fp32 (matters only for non-power-of-2 head counts)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    n2 = 2 ** int(np.floor(np.log2(num_heads)))
    slopes = pow2_slopes(n2)
    if n2 != num_heads:
        extra = pow2_slopes(2 * n2)[0::2][: num_heads - n2]
        slopes = np.concatenate([slopes, extra])
    if not bf16_round:
        return slopes.astype(np.float32)
    # HF build_alibi_tensor rounds the slopes through bfloat16 — match it so
    # converted checkpoints reproduce logits bit-closely
    import ml_dtypes

    return slopes.astype(ml_dtypes.bfloat16).astype(np.float32)


_FLASH_FALLBACK_WARNED = set()


def _warn_flash_fallback(reason: str) -> None:
    """One-time notice when ``attn_impl: flash`` was requested but a feature
    the Pallas kernel doesn't take forces the XLA path — silent degradation
    was the r2-r5 failure mode that kept real configs off the kernel."""
    if reason in _FLASH_FALLBACK_WARNED:
        return
    _FLASH_FALLBACK_WARNED.add(reason)
    from ..utils.logging import logger

    logger.warning(
        f"attn_impl=flash requested but {reason} is unsupported by the "
        f"Pallas flash kernel — using the XLA attention for these call "
        f"sites (one-time notice)")


def resolve_attn_impl(impl: str, seq: int, *, biased: bool = False) -> str:
    """``auto|xla|flash`` -> the attention this call site runs, in the
    ``resolve_loss_impl`` order: an explicit model field wins, then the fleet
    knob (``ops/fastpath.py``), then the heuristic — flash on a real
    accelerator when the sequence tiles cleanly; the XLA reference (O(S^2)
    logits) on CPU tests, odd lengths, and ``biased`` sites (ALiBi or a local
    window: the flash kernel takes no additive bias)."""
    if impl == "auto":
        from ..ops.fastpath import fastpath

        impl = fastpath("attn_impl")
    if impl == "auto":
        impl = ("flash" if jax.default_backend() != "cpu" and seq % 128 == 0
                and not biased else "xla")
    return impl


def _flash_attention(q, k, v, *, causal: bool, scale):
    """The Pallas flash kernel on ``[B, S, H, D]`` operands. GSPMD cannot
    partition a Mosaic kernel (lowering refuses it on any multi-device
    mesh), so there the call is wrapped in a shard_map: batch over the dp
    axes, heads over tp, a dim that does not divide its axes replicated —
    and under GQA q and kv heads shard together or not at all, since the
    kernel maps query head ``h`` to kv head ``h // group`` by local index.
    Inside an enclosing manual region (Ulysses, the SPMD pipeline body) the
    operands are already per-shard and the kernel is called directly."""
    from ..ops.pallas.flash_attention import flash_attention
    from ..parallel.topology import TP_AXIS, get_topology
    from ..utils.shard_map_compat import manual_axes, shard_map_nocheck

    kernel = partial(flash_attention, causal=causal, sm_scale=scale)
    topo = get_topology()
    if topo.n_devices == 1 or manual_axes():
        return kernel(q, k, v)
    spec = sites.heads_sharded_act(topo.dp_axes, TP_AXIS)
    q_spec = topo.filter_spec(spec, q.shape)
    kv_spec = topo.filter_spec(spec, k.shape)
    if q_spec[2] is None or kv_spec[2] is None:
        q_spec = kv_spec = topo.filter_spec(
            sites.heads_sharded_act(topo.dp_axes, None), q.shape)
    return shard_map_nocheck(kernel, topo.mesh, (q_spec, kv_spec, kv_spec),
                             q_spec)(q, k, v)


def attention_core(q, k, v, *, causal: bool = True, impl: str = "auto",
                   positions_q=None, positions_kv=None, alibi=None,
                   scale=None, window=None, alibi_post_scale=False):
    """[B, S, H, D] attention. ``flash`` uses the Pallas kernel on TPU
    (native GQA + ``sm_scale`` — kv heads are never repeat-materialized);
    ``xla`` is the jnp reference (fused well by XLA on small shapes), which
    also indexes kv heads directly via a grouped einsum under GQA.
    ``alibi``: per-head slopes [H] — adds ``-slope * (pos_q - pos_k)`` to the
    logits (Press et al.; reference bloom/falcon containers).
    ``scale``: logits multiplier (default 1/sqrt(d); gpt-neo uses 1.0).
    ``window``: local attention — key j visible iff q_pos - j < window."""
    if impl == "flash":
        if alibi is None and window is None:
            return _flash_attention(q, k, v, causal=causal, scale=scale)
        _warn_flash_fallback("an ALiBi bias" if alibi is not None
                             else "a local attention window")
    b, sq, h, d = q.shape
    skv, hk = k.shape[1], k.shape[2]
    scale = (1.0 / np.sqrt(d)) if scale is None else float(scale)
    pq = positions_q if positions_q is not None else jnp.arange(sq)[:, None]
    pk = positions_kv if positions_kv is not None else jnp.arange(skv)[None, :]
    # falcon/bloom apply the alibi bias BEFORE the 1/sqrt(d) scaling (HF
    # modeling_falcon.py: (scores + alibi) * inv_norm_factor) — fold the
    # scale into the slope to match; MPT adds the raw slope AFTER scaling
    sl_factor = 1.0 if alibi_post_scale else scale
    if hk != h:
        # GQA without materializing repeated kv heads: group the q heads per
        # kv head (the cached_attention layout) so the kv operands stream at
        # their true size — logits [b, hk, rep, sq, skv]
        rep = h // hk
        qg = q.reshape(b, sq, hk, rep, d)
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
        if alibi is not None:
            dist = (pq - pk).astype(jnp.float32)             # [sq, skv]
            sl = (sl_factor * jnp.asarray(alibi)).reshape(hk, rep)
            logits = logits - sl[None, :, :, None, None] * dist[None, None, None]
        if causal:
            mask = pq >= pk
            if window is not None:
                mask = mask & (pq - pk < window)
            logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
        return out.reshape(b, sq, h, d)
    # fp32 accumulation off the MXU (free on TPU), so softmax sees full precision
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if alibi is not None:
        dist = (pq - pk).astype(jnp.float32)                 # [sq, skv]
        logits = logits - (sl_factor * jnp.asarray(alibi))[None, :, None, None] * dist[None, None]
    if causal:
        mask = pq >= pk  # [sq, skv]
        if window is not None:
            mask = mask & (pq - pk < window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _update_cache(cache_kv, new_kv, cache_index):
    """Write ``new_kv [B,S,Hk,D]`` into ``cache_kv [B,M,Hk,D]`` at per-sequence
    offsets ``cache_index [B]`` (the v1 inference KV-cache append; reference
    fused attention kernels do this in-place, ``csrc/transformer/inference``)."""
    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (i, 0, 0))

    return jax.vmap(upd)(cache_kv, new_kv, cache_index)


def cached_attention(q, k_cache, v_cache, q_pos, alibi=None, scale=None,
                     window=None, alibi_post_scale=False, kv_pos=None,
                     kv_valid=None, return_stats=False):
    """Decode attention over a KV buffer with per-sequence validity.

    q: [B,S,H,D]; caches: [B,M,Hk,D]; q_pos: [B,S] absolute positions.
    ``kv_pos`` [B, M] gives each slot's absolute position (default: the slot
    index — the dense cache layout); ``kv_valid`` [B, M] restricts readable
    slots (default: all). Slot j attends iff valid, ``pos_j <= q_pos`` and
    within the local ``window``. GQA is handled by grouping query heads per
    kv head — no materialized kv-head replication. ``return_stats`` adds the
    online-softmax (m, l) per row ([B,S,H] fp32) for partial-attention
    merges (the frozen-cache decode path)."""
    b, s, h, d = q.shape
    m, hk = k_cache.shape[1], k_cache.shape[2]
    rep = h // hk
    qg = q.reshape(b, s, hk, rep, d)
    scale = (1.0 / np.sqrt(d)) if scale is None else float(scale)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_cache.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if kv_pos is None:
        slot = jnp.arange(m)[None, None, None, None, :]
    else:
        slot = kv_pos[:, None, None, None, :]
    if alibi is not None:
        # pre- vs post-scaling bias convention (see attention_core)
        sl_factor = 1.0 if alibi_post_scale else scale
        dist = (q_pos[:, None, None, :, None] - slot).astype(jnp.float32)
        sl = sl_factor * jnp.asarray(alibi).reshape(hk, rep)
        logits = logits - sl[None, :, :, None, None] * dist
    mask = slot <= q_pos[:, None, None, :, None]
    if window is not None:
        mask = mask & (q_pos[:, None, None, :, None] - slot < window)
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    if not return_stats:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_cache.astype(q.dtype))
        return out.reshape(b, s, h, d)
    m_row = jnp.max(logits, axis=-1)                          # [b,hk,rep,s]
    p = jnp.where(mask, jnp.exp(logits - m_row[..., None]), 0.0)
    l_row = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(q.dtype),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    safe = jnp.where(l_row == 0.0, 1.0, l_row)
    out = (acc / jnp.transpose(safe, (0, 3, 1, 2))[..., None]).astype(q.dtype)
    stats = lambda a: jnp.transpose(a, (0, 3, 1, 2)).reshape(b, s, h)
    return out.reshape(b, s, h, d), stats(m_row), stats(l_row)


def merge_partial_attention(out1, m1, l1, out2, m2, l2):
    """Merge two normalized partial-attention results over disjoint KV sets
    (flash combine algebra). out_i: [..., D]; m_i/l_i: [...]; an empty set
    contributes ``m = -inf, l = 0``."""
    mx = jnp.maximum(m1, m2)
    e1 = l1 * jnp.exp(m1 - mx)
    e2 = l2 * jnp.exp(m2 - mx)
    den = jnp.maximum(e1 + e2, 1e-30)
    num = (out1.astype(jnp.float32) * e1[..., None]
           + out2.astype(jnp.float32) * e2[..., None])
    return num / den[..., None]


# ---------------------------------------------------------------------------
# Ring-overlapped collective matmul wiring (ops/collective_matmul.py).
# The flax modules express TP declaratively (param_specs + GSPMD inserts the
# collectives); with the overlap knob on, the column/row-parallel matmuls
# instead run inside an explicit shard_map where the tp (or Ulysses sp)
# collective is decomposed into ppermute ring chunks interleaved with the
# partial matmuls — T3-style latency hiding. Activations cross the block
# sequence-sharded over the axis (Megatron-SP layout), so consecutive
# layers chain gather->matmul / matmul->scatter without extra reshards.
# Any shape that doesn't chunk evenly falls back to the declarative path.
# ---------------------------------------------------------------------------


def _overlap_active(cfg) -> bool:
    if cfg.overlap_collective_matmul:
        return True
    from ..ops.collective_matmul import overlap_enabled

    return overlap_enabled()


def _overlap_ctx(cfg, x, mod):
    """The live topology when the overlapped path could engage, else None
    (knob off and planner declines, flax init trace, non-[B,S,D] input, or
    a batch that doesn't shard over the dp axes)."""
    if mod.is_initializing() or x.ndim != 3:
        return None
    from ..parallel.topology import get_topology
    from ..utils.shard_map_compat import manual_axes

    if manual_axes():
        # already inside a manual region (e.g. the SPMD pipeline body) —
        # shard_map does not nest; stay declarative there
        return None
    topo = get_topology()
    if x.shape[0] % topo.axis_size(*topo.dp_axes):
        return None
    if not _overlap_active(cfg):
        # comm-planner tp-linear / ulysses site: with the raw knob unset,
        # fused-matmul engagement is the planner's call per mesh + shape
        from ..comm.planner import planner_active, resolve_site

        sp = cfg.sequence_parallel and cfg.sp_impl == "ulysses"
        axis = "sp" if sp else "tp"
        size = topo.sp_size if sp else topo.tp_size
        if not planner_active() or size <= 1:
            return None
        d = resolve_site(op="gather_matmul", shape=x.shape, dtype=x.dtype,
                         axes=(axis,), consumer="ulysses" if sp else "tp-linear")
        if d.impl != "fused_matmul":
            return None
    return topo


def _embed_ring_ctx(cfg, mod, batch_size):
    """The live topology when the ring-overlapped embedding paths could
    engage, else None. The ring runs the Megatron VocabParallelEmbedding
    layout over tp: the table circulates in ppermute chunks while the
    resident chunk's lookups (or the tied head's chunk matmuls) execute
    (ops/collective_matmul.py). Resolution: model field > fleet knob
    (training_fastpath.embedding_overlap) > planner per-site decision."""
    if mod.is_initializing():
        return None
    if "embed" not in mod.variables.get("params", {}):
        return None
    impl = cfg.embed_overlap
    if impl == "auto":
        from ..ops.fastpath import fastpath

        impl = fastpath("embedding_overlap")
    if impl == "xla":
        return None
    from ..utils.shard_map_compat import manual_axes

    if manual_axes():
        return None  # already inside a manual region: stay declarative
    from ..parallel.topology import get_topology

    topo = get_topology()
    from ..ops.collective_matmul import embedding_overlap_ready

    if not embedding_overlap_ready(topo.tp_size, cfg.vocab_size):
        return None
    if batch_size % topo.axis_size(*topo.dp_axes):
        return None
    if impl == "auto":
        # planner site: ring vs xla is a per-topology call (PR 3)
        from ..comm.planner import planner_active, resolve_site

        if not planner_active():
            return None
        d = resolve_site(op="embed_gather",
                         shape=(cfg.vocab_size // topo.tp_size,
                                cfg.hidden_size),
                         dtype=cfg.dtype, axes=("tp",), consumer="embed")
        if d.impl not in ("ring", "bidir_ring"):
            return None
    return topo


class Attention(nn.Module):
    cfg: TransformerConfig
    window: Optional[int] = None   # gpt-neo per-layer local attention

    @nn.compact
    def __call__(self, x, *, deterministic=True, cache=None, cache_index=None,
                 whole_prefill=False, frozen_cache=None, window_kv=None,
                 window_t=None, frozen_len=None):
        cfg = self.cfg
        h, hk, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        rope = partial(apply_rope, interleaved=cfg.rotary_interleaved)
        scale, window = cfg.attn_scale, self.window
        dense = partial(nn.DenseGeneral, use_bias=cfg.qkv_bias,
                        dtype=cfg.dtype, param_dtype=jnp.float32)
        ulysses_mm, tp_mm = self._overlap_mode(x, cache, window_kv)
        if tp_mm:
            q, k, v = self._overlap_qkv(x)
        elif ulysses_mm:
            q = k = v = None  # projections fuse into the Ulysses ring below
        else:
            q = dense(features=(h, d), name="q_proj")(x)
            k = dense(features=(hk, d), name="k_proj")(x)
            v = dense(features=(hk, d), name="v_proj")(x)

        if cfg.position == "rope":
            cos, sin = rope_table(cfg.max_seq_len, cfg.rotary_dim, cfg.rope_theta)
        # mpt (alibi_post_scale) computes slopes in fp32; falcon/bloom round
        # them through bf16 — follow each family's convention
        alibi = (alibi_slopes(h, bf16_round=not cfg.alibi_post_scale)
                 if cfg.position == "alibi" else None)

        o_proj = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                                 use_bias=cfg.out_bias, dtype=cfg.dtype,
                                 param_dtype=jnp.float32, name="o_proj")

        if window_kv is not None:
            # frozen-cache decode (inference v1 generate scan): the prefill
            # cache is READ-ONLY — XLA copies a scanned carry in full on
            # every iteration when scatter/DUS-updated, so only the small
            # in-window buffer rides the scan; attention over the two
            # disjoint KV sets merges with the flash combine algebra.
            positions = cache_index[:, None]                     # [B, 1]
            if cfg.position == "rope":
                q = rope(q, cos, sin, positions)
                k = rope(k, cos, sin, positions)
            wk, wv = window_kv["k"], window_kv["v"]              # [B, W, Hk, D]
            W = wk.shape[1]
            wk = jax.lax.dynamic_update_slice(
                wk, k.astype(wk.dtype), (0, window_t, 0, 0))
            wv = jax.lax.dynamic_update_slice(
                wv, v.astype(wv.dtype), (0, window_t, 0, 0))
            b = x.shape[0]
            mf = frozen_cache["k"].shape[1]
            frozen_valid = (jnp.arange(mf)[None, :]
                            < frozen_len[:, None])               # [B, Mf]
            o1, m1, l1 = cached_attention(
                q, frozen_cache["k"], frozen_cache["v"], positions,
                alibi=alibi, scale=scale, window=window,
                alibi_post_scale=cfg.alibi_post_scale,
                kv_valid=frozen_valid, return_stats=True)
            w_pos = frozen_len[:, None] + jnp.arange(W)[None, :]  # [B, W]
            w_valid = jnp.broadcast_to(
                (jnp.arange(W) <= window_t)[None, :], (b, W))
            o2, m2, l2 = cached_attention(
                q, wk, wv, positions, alibi=alibi, scale=scale, window=window,
                alibi_post_scale=cfg.alibi_post_scale,
                kv_pos=w_pos, kv_valid=w_valid, return_stats=True)
            merged = merge_partial_attention(o1, m1, l1, o2, m2, l2)
            out = o_proj(merged.astype(x.dtype))
            return out, {"k": wk, "v": wv}

        if cache is not None:
            # incremental decoding path (inference v1 engine)
            positions = cache_index[:, None] + jnp.arange(x.shape[1])[None, :]
            if cfg.position == "rope":
                q = rope(q, cos, sin, positions)
                k = rope(k, cos, sin, positions)
            new_cache = {"k": _update_cache(cache["k"], k, cache_index),
                         "v": _update_cache(cache["v"], v, cache_index)}
            if x.shape[1] > 1 and whole_prefill:
                # whole-prompt prefill (caller asserts cache_index==0):
                # attend within the fresh prompt — [S,S] logits, not [S,M]
                # over the cache's unwritten capacity. Without the static
                # whole_prefill promise, chunked multi-token calls take the
                # full-cache path, which is correct for any cache_index.
                out = attention_core(q, k, v, causal=True, impl="xla",
                                     alibi=alibi, scale=scale, window=window,
                                     alibi_post_scale=cfg.alibi_post_scale)
            else:
                out = cached_attention(q, new_cache["k"], new_cache["v"],
                                       positions, alibi=alibi, scale=scale,
                                       window=window,
                                       alibi_post_scale=cfg.alibi_post_scale)
            return o_proj(out), new_cache

        impl = resolve_attn_impl(cfg.attn_impl, x.shape[1],
                                 biased=alibi is not None or window is not None)

        # Ulysses only in real execution: flax init traces tiny batches that
        # need not divide the mesh, and attention adds no params anyway.
        if cfg.sequence_parallel and not self.is_initializing():
            if alibi is not None:
                raise NotImplementedError(
                    "ALiBi + sequence parallelism is unsupported: the "
                    "exchange would need per-shard slope slices")
            if cfg.sp_impl == "ring":
                if window is not None:
                    raise NotImplementedError(
                        "local attention windows + ring SP not supported")
                from ..sequence.ring import ring_attention

                def apply_pos(q_, k_, pos):
                    if cfg.position == "rope":
                        q_ = rope(q_, cos, sin, pos)
                        k_ = rope(k_, cos, sin, pos)
                    return q_, k_

                out = ring_attention(q, k, v, apply_pos=apply_pos,
                                     causal=True, scale=scale)
            else:
                from ..sequence.layer import (ulysses_attention,
                                              ulysses_matmul_attention)

                def local_attn(q_, k_, v_, pos):
                    if cfg.position == "rope":
                        q_ = rope(q_, cos, sin, pos)
                        k_ = rope(k_, cos, sin, pos)
                    return attention_core(q_, k_, v_, causal=True, impl=impl,
                                          scale=scale, window=window)

                if ulysses_mm:
                    # qkv + o projections fused into the sp exchange: the
                    # ring all-gather-matmul/matmul-reduce-scatter replace
                    # the four all-to-alls AND the separate projections
                    p = self.variables["params"]
                    out = ulysses_matmul_attention(
                        local_attn, x, p["q_proj"], p["k_proj"], p["v_proj"],
                        p["o_proj"], dtype=cfg.dtype)
                    if cfg.dropout > 0 and not deterministic:
                        out = nn.Dropout(rate=cfg.dropout)(
                            out, deterministic=False)
                    return out
                out = ulysses_attention(local_attn, q, k, v)
        else:
            if cfg.position == "rope":
                q = rope(q, cos, sin)
                k = rope(k, cos, sin)
            out = attention_core(q, k, v, causal=True, impl=impl, alibi=alibi,
                                 scale=scale, window=window,
                                 alibi_post_scale=cfg.alibi_post_scale)

        out = self._overlap_o(out) if tp_mm else o_proj(out)
        if cfg.dropout > 0 and not deterministic:
            out = nn.Dropout(rate=cfg.dropout)(out, deterministic=False)
        return out

    # -- ring-overlapped collective matmul paths ---------------------------

    def _overlap_mode(self, x, cache, window_kv):
        """(ulysses_mm, tp_mm): which overlapped projection path applies.
        Decode/cache paths and ragged shapes stay on the declarative path."""
        cfg = self.cfg
        if cache is not None or window_kv is not None:
            return False, False
        topo = _overlap_ctx(cfg, x, self)
        if topo is None or "q_proj" not in self.variables.get("params", {}):
            return False, False
        h, hk, s = cfg.num_heads, cfg.kv_heads, x.shape[1]
        from ..ops.collective_matmul import overlap_ready

        if cfg.sequence_parallel:
            ok = (cfg.sp_impl == "ulysses" and topo.tp_size == 1
                  and cfg.position != "alibi"
                  and overlap_ready(topo.sp_size, h, hk, s))
            return ok, False
        ok = topo.sp_size == 1 and overlap_ready(topo.tp_size, h, hk, s)
        return False, ok

    def _overlap_qkv(self, x):
        """Fused qkv: one ring all-gather-matmul over tp — x arrives
        sequence-sharded (the previous row-parallel output's layout), the
        gather hides behind the three projections run as one matmul."""
        from ..ops.collective_matmul import fused_qkv_all_gather_matmul
        from ..parallel.topology import TP_AXIS, get_topology
        from ..utils.shard_map_compat import shard_map_nocheck

        cfg = self.cfg
        dt, dh = cfg.dtype, cfg.head_dim
        topo = get_topology()
        dp = topo.dp_axes
        params = self.variables["params"]
        wq, wk, wv = (params[n]["kernel"].astype(dt)
                      for n in ("q_proj", "k_proj", "v_proj"))
        w_spec = sites.col_kernel3(TP_AXIS)
        args = [x.astype(dt), wq, wk, wv]
        specs = [sites.seq_sharded_act(dp, TP_AXIS), w_spec, w_spec, w_spec]
        if cfg.qkv_bias:
            args += [params[n]["bias"].astype(dt)
                     for n in ("q_proj", "k_proj", "v_proj")]
            specs += [sites.col_bias2(TP_AXIS)] * 3

        def body(x_, wq_, wk_, wv_, *bs):
            return fused_qkv_all_gather_matmul(x_, wq_, wk_, wv_, bs, dh,
                                               TP_AXIS)

        head_spec = sites.heads_sharded_act(dp, TP_AXIS)
        return shard_map_nocheck(body, topo.mesh, tuple(specs),
                                 (head_spec, head_spec, head_spec))(*args)

    def _overlap_o(self, out):
        """Row-parallel output projection as a ring matmul-reduce-scatter:
        the tp reduction hides behind the chunked o matmul and the result
        leaves sequence-sharded for the next block's gather."""
        from ..ops.collective_matmul import matmul_reduce_scatter
        from ..parallel.topology import TP_AXIS, get_topology
        from ..utils.shard_map_compat import shard_map_nocheck

        cfg = self.cfg
        dt = cfg.dtype
        topo = get_topology()
        dp = topo.dp_axes
        params = self.variables["params"]["o_proj"]
        wo = params["kernel"].astype(dt)  # [H, Dh, D]

        def body(o_, wo_):
            hl, dhl = wo_.shape[:2]
            b_, s_ = o_.shape[:2]
            return matmul_reduce_scatter(o_.reshape(b_, s_, hl * dhl),
                                         wo_.reshape(hl * dhl, -1), TP_AXIS)

        y = shard_map_nocheck(body, topo.mesh,
                              (sites.heads_sharded_act(dp, TP_AXIS),
                               sites.row_kernel3(TP_AXIS)),
                              sites.seq_sharded_act(dp, TP_AXIS))(
                                  out.astype(dt), wo)
        if cfg.out_bias:
            y = y + params["bias"].astype(dt)
        return y


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        bias = cfg.ffn_bias
        topo = _overlap_ctx(cfg, x, self)
        if topo is not None and self._overlap_ok(topo, x):
            return self._overlapped(topo, x)
        if cfg.activation == "swiglu":
            gate = nn.Dense(cfg.intermediate_size, use_bias=bias, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name="gate_proj")(x)
            up = nn.Dense(cfg.intermediate_size, use_bias=bias, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="up_proj")(x)
            hidden = nn.silu(gate) * up
        else:
            hidden = nn.Dense(cfg.intermediate_size, use_bias=bias, dtype=cfg.dtype,
                              param_dtype=jnp.float32, name="up_proj")(x)
            hidden = apply_activation(cfg.activation, hidden)
        return nn.Dense(cfg.hidden_size, use_bias=bias, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name="down_proj")(hidden)

    # -- ring-overlapped collective matmul path ----------------------------

    def _overlap_ok(self, topo, x):
        from ..ops.collective_matmul import overlap_ready

        return (topo.sp_size == 1
                and overlap_ready(topo.tp_size, x.shape[1],
                                  self.cfg.intermediate_size)
                and "down_proj" in self.variables.get("params", {}))

    def _overlapped(self, topo, x):
        """Column linear as ring all-gather-matmul (gate|up fused into one
        gather), row linear as ring matmul-reduce-scatter — the tp
        collectives hide behind the partial matmuls, and activations cross
        the MLP sequence-sharded over tp (Megatron-SP layout)."""
        from ..ops.collective_matmul import (all_gather_matmul,
                                             matmul_reduce_scatter)
        from ..parallel.topology import TP_AXIS
        from ..utils.shard_map_compat import shard_map_nocheck

        cfg = self.cfg
        dt = cfg.dtype
        params = self.variables["params"]
        gated = cfg.activation == "swiglu"
        col_names = ("gate_proj", "up_proj") if gated else ("up_proj",)
        n_col = len(col_names)
        has_bias = "bias" in params[col_names[0]]
        dp = topo.dp_axes
        args = [x.astype(dt)]
        specs = [sites.seq_sharded_act(dp, TP_AXIS)]
        for name in col_names:
            args.append(params[name]["kernel"].astype(dt))
            specs.append(sites.col_kernel2(TP_AXIS))
        args.append(params["down_proj"]["kernel"].astype(dt))
        specs.append(sites.row_kernel2(TP_AXIS))
        if has_bias:
            for name in col_names:
                args.append(params[name]["bias"].astype(dt))
                specs.append(sites.col_bias1(TP_AXIS))

        def body(x_, *rest):
            cols, wd_ = rest[:n_col], rest[n_col]
            bs = rest[n_col + 1:]
            # local concat keeps each rank's [gate_shard | up_shard] layout
            h = all_gather_matmul(x_, jnp.concatenate(cols, axis=-1), TP_AXIS)
            if bs:
                h = h + jnp.concatenate(bs, axis=-1)
            if gated:
                g, u = jnp.split(h, 2, axis=-1)
                h = nn.silu(g) * u
            else:
                h = apply_activation(cfg.activation, h)
            return matmul_reduce_scatter(h, wd_, TP_AXIS)

        out = shard_map_nocheck(body, topo.mesh, tuple(specs),
                                sites.seq_sharded_act(dp, TP_AXIS))(*args)
        if has_bias:
            out = out + params["down_proj"]["bias"].astype(dt)
        return out


class Block(nn.Module):
    cfg: TransformerConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, deterministic=True, cache=None, cache_index=None,
                 whole_prefill=False, frozen_cache=None, window_kv=None,
                 window_t=None, frozen_len=None):
        # (x, deterministic) stay positional for nn.remat static_argnums
        cfg = self.cfg
        y = _norm(cfg, "attn_norm")(x)
        window = None
        if cfg.layer_windows is not None:
            window = cfg.layer_windows[self.layer_idx]
        attn = Attention(cfg, window=window, name="attn")
        if window_kv is not None:
            attn_out, new_cache = attn(y, deterministic=deterministic,
                                       cache_index=cache_index,
                                       frozen_cache=frozen_cache,
                                       window_kv=window_kv, window_t=window_t,
                                       frozen_len=frozen_len)
        elif cache is not None:
            attn_out, new_cache = attn(y, deterministic=deterministic,
                                       cache=cache, cache_index=cache_index,
                                       whole_prefill=whole_prefill)
        else:
            attn_out, new_cache = attn(y, deterministic=deterministic), None

        def mlp_of(z):
            use_moe = cfg.num_experts > 0 and (
                self.layer_idx % cfg.moe_every == cfg.moe_offset % cfg.moe_every)
            if use_moe:
                from ..moe.layer import MoEBlock

                out, aux = MoEBlock(cfg, name="moe")(z)
                self.sow("intermediates", "moe_aux_loss", aux)
                return out
            return MLP(cfg, name="mlp")(z)

        if cfg.parallel_residual:
            # falcon / gpt-neox: attn and mlp both branch off x and sum into
            # the residual; falcon-7b feeds BOTH from one norm
            y_mlp = y if cfg.parallel_shared_norm else _norm(cfg, "mlp_norm")(x)
            out = x + attn_out + mlp_of(y_mlp)
        else:
            x = x + attn_out
            out = x + mlp_of(_norm(cfg, "mlp_norm")(x))
        if cache is not None or window_kv is not None:
            return out, new_cache
        return out


class TransformerLM(nn.Module):
    """Causal LM. ``__call__(tokens [B,S]) -> logits [B,S,V] (fp32)``."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, deterministic=True, cache=None, cache_index=None,
                 whole_prefill=False, frozen_cache=None, window=None,
                 window_t=None, frozen_len=None, return_hidden=False):
        """Training/eval: ``logits = __call__(tokens)``. Incremental decode
        (inference v1): pass ``cache`` (see ``init_kv_cache``) + per-sequence
        write offsets ``cache_index [B]`` → ``(logits, new_cache)``.
        Frozen-cache decode (the generate scan): pass the read-only prefill
        ``frozen_cache``, the per-layer in-``window`` KV pytree, the step
        index ``window_t`` and per-sequence prompt lengths ``frozen_len`` →
        ``(logits, new_window)``."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="embed")
        x = None
        if cache is None and window is None and tokens.ndim == 2:
            # training path: ring-overlapped vocab-sharded gather when the
            # knob/planner picks it (decode paths stay declarative)
            x = self._embed_table_ring(tokens)
        if x is None:
            x = embed(tokens)
        if cfg.embed_norm:  # bloom word_embeddings_layernorm
            x = _norm(cfg, "embed_norm")(x)
        if cfg.position == "learned":
            pos_emb = self.param("pos_embed", nn.initializers.normal(0.02),
                                 (cfg.max_seq_len + cfg.pos_offset,
                                  cfg.hidden_size), jnp.float32)
            off = cfg.pos_offset  # OPT embeds positions shifted by 2
            if cache is not None or window is not None:
                positions = cache_index[:, None] + jnp.arange(tokens.shape[1])[None, :]
                x = x + pos_emb[positions + off].astype(cfg.dtype)
            else:
                x = x + pos_emb[None, off:off + x.shape[1]].astype(cfg.dtype)

        block = Block
        if cfg.remat and cache is None:
            policy = None
            if cfg.remat_policy:
                policy = getattr(jax.checkpoint_policies, cfg.remat_policy)
            block = nn.remat(Block, policy=policy, static_argnums=(2,))
        new_cache = {}
        for i in range(cfg.num_layers):
            name = f"layer_{i}"
            if window is not None:
                x, new_cache[name] = block(cfg, i, name=name)(
                    x, deterministic, cache_index=cache_index,
                    frozen_cache=frozen_cache[name], window_kv=window[name],
                    window_t=window_t, frozen_len=frozen_len)
            elif cache is not None:
                x, new_cache[name] = block(cfg, i, name=name)(
                    x, deterministic, cache=cache[name], cache_index=cache_index,
                    whole_prefill=whole_prefill)
            else:
                x = block(cfg, i, name=name)(x, deterministic)
        x = _norm(cfg, "final_norm")(x)
        if cfg.no_lm_head or return_hidden:  # clip text / vocab-parallel loss
            return (x, new_cache) if (cache is not None or window is not None) else x
        if cfg.tie_embeddings:
            logits = None
            if cache is None and window is None:
                logits = self._tied_head_ring(x)  # the gather's transpose
            if logits is None:
                logits = embed.attend(x.astype(jnp.float32))
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias,
                              dtype=jnp.float32,
                              param_dtype=jnp.float32, name="lm_head")(x.astype(jnp.float32))
        if cache is not None or window is not None:
            return logits, new_cache
        return logits

    # -- ring-overlapped embedding paths (ops/collective_matmul.py) --------

    def _embed_table_ring(self, tokens):
        """[B, S] -> [B, S, E] via ring_embedding_gather, or None when the
        knob/planner/topology says the declarative gather stays."""
        cfg = self.cfg
        topo = _embed_ring_ctx(cfg, self, tokens.shape[0])
        if topo is None:
            return None
        from ..ops.collective_matmul import ring_embedding_gather
        from ..parallel.topology import TP_AXIS
        from ..utils.shard_map_compat import shard_map_nocheck

        table = self.variables["params"]["embed"]["embedding"]
        dp = topo.dp_axes

        def body(tok, tab):
            return ring_embedding_gather(tok, tab, TP_AXIS)

        return shard_map_nocheck(body, topo.mesh,
                                 (sites.tokens_act(dp),
                                  sites.vocab_sharded_table(TP_AXIS)),
                                 sites.embed_act(dp))(
                                     tokens, table.astype(cfg.dtype))

    def _tied_head_ring(self, x):
        """Tied lm head as the embedding ring's transpose: logits [.., V]
        from the vocab-sharded table via ring_tied_lm_head, or None."""
        cfg = self.cfg
        if x.ndim != 3:
            return None
        topo = _embed_ring_ctx(cfg, self, x.shape[0])
        if topo is None:
            return None
        from ..ops.collective_matmul import ring_tied_lm_head
        from ..parallel.topology import TP_AXIS
        from ..utils.shard_map_compat import shard_map_nocheck

        table = self.variables["params"]["embed"]["embedding"]
        dp = topo.dp_axes

        def body(x_, tab):
            return ring_tied_lm_head(x_, tab, TP_AXIS)

        # operands in cfg.dtype — nn.Embed.attend's promote_dtype convention
        return shard_map_nocheck(body, topo.mesh,
                                 (sites.embed_act(dp),
                                  sites.vocab_sharded_table(TP_AXIS)),
                                 sites.embed_act(dp))(
                                     x.astype(cfg.dtype),
                                     table.astype(cfg.dtype))


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: Optional[int] = None,
                  dtype=None):
    """Dense per-layer KV cache ``{layer_i: {k,v: [B, M, Hk, D]}}`` (the v1
    inference cache; the paged/v2 cache lives in ``inference/v2/ragged``)."""
    m = max_len or cfg.max_seq_len
    dt = dtype or cfg.dtype
    shape = (batch, m, cfg.kv_heads, cfg.head_dim)
    return {f"layer_{i}": {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for i in range(cfg.num_layers)}


def kv_cache_specs(cfg: TransformerConfig, tp_axis: str = "tp", dp_axis=None):
    """PartitionSpecs for the v1 cache: batch over dp, kv heads over tp."""
    spec = sites.kv_cache_entry(dp_axis, tp_axis)
    return {f"layer_{i}": {"k": spec, "v": spec} for i in range(cfg.num_layers)}


# ---------------------------------------------------------------------------
# Loss + init + TP specs
# ---------------------------------------------------------------------------


def causal_lm_loss(logits, tokens, loss_mask=None, z_loss: float = 0.0):
    """Next-token cross entropy; ignores the final position."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss > 0:
        nll = nll + z_loss * jnp.square(logz)
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(nll.dtype)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


def make_loss_fn(model: TransformerLM):
    """Engine-compatible ``loss = f(params, batch, rng)``; adds MoE aux loss.

    With ``cfg.vocab_parallel_loss`` the lm-head matmul + CE run vocab-sharded
    over tp via ``sequence.sharded_lm_loss`` — full-vocab logits are never
    materialised (reference ``sequence/cross_entropy.py`` capability).
    """
    cfg = model.cfg
    if cfg.vocab_parallel_loss and cfg.no_lm_head:
        raise ValueError("vocab_parallel_loss needs an lm head; "
                         "no_lm_head=True models have no vocab projection")

    def _head_kernel_bias(params):
        if cfg.tie_embeddings:
            return params["embed"]["embedding"].T, None
        head = params["lm_head"]
        return head["kernel"], head.get("bias")

    def _headless():
        """True when the loss should consume hidden states + the head kernel
        (never materializing [B, S, V] logits): the vocab-parallel knob, or
        the fused Pallas loss resolving active (docs/training_fastpath.md).
        Evaluated at trace time so the fleet knob set by initialize() is
        seen; tp > 1 without vocab_parallel_loss keeps the dense path (the
        vocab may not shard)."""
        if cfg.vocab_parallel_loss:
            return True
        if cfg.no_lm_head or cfg.lm_head_bias:
            return False
        from ..parallel.topology import get_topology
        from ..sequence.cross_entropy import resolve_loss_impl

        if get_topology().tp_size != 1:
            return False
        return resolve_loss_impl(cfg.loss_impl, cfg.vocab_size) == "fused"

    def _ce(out, params, tokens, mask, headless):
        if headless:
            from ..sequence.cross_entropy import sharded_lm_loss
            kernel, bias = _head_kernel_bias(params)
            return sharded_lm_loss(out, kernel, tokens, loss_mask=mask,
                                   head_bias=bias, loss_impl=cfg.loss_impl)
        return causal_lm_loss(out, tokens, mask)

    def loss_fn(params, batch, rng=None):
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        mask = batch.get("loss_mask") if isinstance(batch, dict) else None
        headless = _headless()
        kwargs = {"return_hidden": True} if headless else {}
        deterministic = True
        if rng is not None and cfg.dropout > 0:
            kwargs["rngs"] = {"dropout": rng}
            deterministic = False
        if cfg.num_experts > 0:
            out, mod_vars = model.apply({"params": params}, tokens,
                                        deterministic=deterministic,
                                        mutable=["intermediates"], **kwargs)
            flat = jax.tree_util.tree_flatten_with_path(mod_vars.get("intermediates", {}))[0]
            aux_losses = [leaf for path, leaf in flat
                          if any("moe_aux_loss" in str(getattr(e, "key", e)) for e in path)]
            aux = sum(aux_losses) / max(len(aux_losses), 1) if aux_losses else 0.0
            return _ce(out, params, tokens, mask, headless) + aux
        out = model.apply({"params": params}, tokens, deterministic=deterministic, **kwargs)
        return _ce(out, params, tokens, mask, headless)

    # TransformerLM's wiring reads the topology itself (TP fast paths, ring
    # overlaps); the engine must not demand explicit specs for it
    loss_fn._sharding_native = True
    return loss_fn


def stack_transformer_params(params, cfg: TransformerConfig):
    """Re-layout TransformerLM params for the SPMD pipeline: per-layer
    ``layer_i`` subtrees stack into ``blocks`` ``[L, ...]`` arrays; embedding
    goes to ``embed``, final norm + lm head to ``head`` (the analogue of
    handing a layer list to ``PipelineModule``, reference ``module.py:86``).

    Requires homogeneous layers (stacking needs one structure). Tied
    embeddings are supported (reference ``TiedLayerSpec``): the table lives
    ONLY under ``embed`` and the head re-reads it (``head_loss_fn`` receives
    the full extra tree when ``tied_head=True``); both stages' gradient
    contributions psum over pp via shard_map's replicated-input transpose —
    exactly the reference's tied-weight allreduce
    (``_exec_reduce_tied_grads``, pipe/engine.py:275).
    """
    layers = [params[f"layer_{i}"] for i in range(cfg.num_layers)]
    structs = {jax.tree.structure(l) for l in layers}
    if len(structs) > 1:
        raise ValueError("pipeline stacking needs homogeneous layers (mixed "
                         "MoE/dense stacks can't share one stage program); "
                         "set moe_every=1 or num_experts=0")
    blocks = jax.tree.map(lambda *ls: jnp.stack(ls), *layers)
    embed = {"embed": params["embed"]}
    if cfg.embed_norm:
        embed["embed_norm"] = params["embed_norm"]
    if cfg.position == "learned":
        embed["pos_embed"] = params["pos_embed"]
    head = {"final_norm": params["final_norm"]}
    if not cfg.tie_embeddings:
        head["lm_head"] = params["lm_head"]
    return {"embed": embed, "blocks": blocks, "head": head}


def transformer_pipeline_fns(cfg: TransformerConfig):
    """(embed_fn, block_fn, head_loss_fn) for ``make_pipeline_loss_fn`` over
    the real TransformerLM block (same math as ``TransformerLM.__call__``,
    expressed per pipeline stage). MoE aux losses are sown into a collection
    the pipeline does not thread, so they are excluded here (dense CE only).
    """
    if cfg.layer_windows is not None and len(set(cfg.layer_windows)) > 1:
        raise ValueError(
            "pipeline bridge runs ONE stacked block program for all layers; "
            "per-layer attention windows (layer_windows with mixed values, "
            "gpt-neo style) cannot vary across a scanned stack — use the "
            "non-pipeline model or a uniform window")
    # a uniform window flows through Block(layer_idx=0) reading layer_windows[0]
    block_mod = Block(cfg, layer_idx=0)
    final_norm_mod = _norm(cfg, "final_norm")  # same module the model uses
    embed_norm_mod = _norm(cfg, "embed_norm") if cfg.embed_norm else None

    def embed_fn(p, mb):
        tokens = mb["tokens"] if isinstance(mb, dict) else mb
        x = p["embed"]["embedding"].astype(cfg.dtype)[tokens]
        if embed_norm_mod is not None:  # bloom word_embeddings_layernorm
            x = embed_norm_mod.apply({"params": p["embed_norm"]}, x)
        if cfg.position == "learned":
            off = cfg.pos_offset
            x = x + p["pos_embed"][off: off + tokens.shape[1]].astype(cfg.dtype)
        return x

    def block_fn(lp, x):
        return block_mod.apply({"params": lp}, x, True)

    def head_loss_fn(p, x, mb):
        tokens = mb["tokens"] if isinstance(mb, dict) else mb
        mask = mb.get("loss_mask") if isinstance(mb, dict) else None
        if cfg.tie_embeddings:
            # tied head (make_pipeline_loss_fn auto-detects via the
            # _tied_head attribute below, so p is the FULL extra tree):
            # logits reuse the stage-0 embedding table; its two gradient
            # contributions psum over pp automatically. Matmul in cfg.dtype
            # to match the dense path's nn.Embed.attend promotion.
            x = final_norm_mod.apply({"params": p["head"]["final_norm"]}, x)
            table = p["embed"]["embed"]["embedding"].astype(cfg.dtype)
            logits = (x.astype(cfg.dtype) @ table.T).astype(jnp.float32)
        else:
            x = final_norm_mod.apply({"params": p["final_norm"]}, x)
            logits = x.astype(jnp.float32) @ p["lm_head"]["kernel"].astype(jnp.float32)
            if "bias" in p["lm_head"]:  # gptj/phi biased lm_head
                logits = logits + p["lm_head"]["bias"].astype(jnp.float32)
        return causal_lm_loss(logits, tokens, mask)

    # make_pipeline_loss_fn reads this to pick the head calling convention —
    # deriving it here removes the two-flags-must-agree failure mode
    head_loss_fn._tied_head = cfg.tie_embeddings
    return embed_fn, block_fn, head_loss_fn


def init_params(model: TransformerLM, seed: int = 0, batch: int = 2, seq: Optional[int] = None):
    seq = seq or min(model.cfg.max_seq_len, 128)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), tokens)["params"]


def param_specs(params, tp_axis: str = "tp") -> Any:
    """Megatron-style TP PartitionSpecs by parameter path (reference AutoTP
    ``module_inject/auto_tp.py:189`` infers the same split from layer names):
    q/k/v/gate/up column-parallel (shard output dim), o/down row-parallel
    (shard input dim), embeddings sharded over vocab/hidden, experts over 'ep'.

    Delegates to the declarative generic rule pack
    (``sharding/packs.py::generic_pack``) — the pack is this function's
    historical if/elif ladder made explicit, and stays bitwise-identical
    to it (pinned by ``tests/unit/test_sharding_rules.py``).
    """
    from ..sharding.packs import generic_pack

    pack = generic_pack()
    if tp_axis != "tp":
        pack = pack.renamed({"tp": tp_axis})
    return pack.match(params)


# ---------------------------------------------------------------------------
# Family presets (reference model-implementations inventory, SURVEY.md §2.6)
# ---------------------------------------------------------------------------


def gpt2_config(size: str = "small", **overrides) -> TransformerConfig:
    dims = {"small": (768, 12, 12), "medium": (1024, 24, 16), "large": (1280, 36, 20),
            "xl": (1600, 48, 25)}[size]
    d, l, h = dims
    base = dict(vocab_size=50257, hidden_size=d, intermediate_size=4 * d, num_layers=l,
                num_heads=h, max_seq_len=1024, norm="layernorm", activation="gelu",
                position="learned", tie_embeddings=True)
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config(size: str = "7b", **overrides) -> TransformerConfig:
    dims = {"tiny": (256, 4, 8, 8, 688), "1b": (2048, 22, 32, 4, 5632),
            "7b": (4096, 32, 32, 32, 11008), "13b": (5120, 40, 40, 40, 13824)}[size]
    d, l, h, hk, f = dims
    base = dict(vocab_size=32000, hidden_size=d, intermediate_size=f, num_layers=l,
                num_heads=h, num_kv_heads=hk, max_seq_len=4096, norm="rmsnorm",
                activation="swiglu", position="rope")
    base.update(overrides)
    return TransformerConfig(**base)


def mixtral_config(size: str = "tiny", **overrides) -> TransformerConfig:
    dims = {"tiny": (256, 4, 8, 8, 512, 4), "8x7b": (4096, 32, 32, 8, 14336, 8)}[size]
    d, l, h, hk, f, e = dims
    base = dict(vocab_size=32000, hidden_size=d, intermediate_size=f, num_layers=l,
                num_heads=h, num_kv_heads=hk, max_seq_len=4096, norm="rmsnorm",
                activation="swiglu", position="rope", num_experts=e, moe_top_k=2)
    base.update(overrides)
    return TransformerConfig(**base)
