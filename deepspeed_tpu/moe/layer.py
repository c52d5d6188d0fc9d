"""MoE block module (reference ``MoE``, ``deepspeed/moe/layer.py:17`` +
``MOELayer``, ``sharded_moe.py:533``).

Expert parallelism TPU-style: expert weights are stacked ``[E, ...]`` arrays
sharded over the ``ep`` mesh axis (see ``models/transformer.py::param_specs``);
dispatching tokens to experts is an einsum into expert-major layout with a
sharding constraint, which XLA lowers to the same all-to-all pattern the
reference issues via ``_AllToAll`` (``sharded_moe.py:96``). Expert-vs-dense
gradient separation (reference ``engine._reduce_expert_gradients:2510``) is
automatic: expert params are sharded over ``ep``, so SPMD autodiff reduces
their grads only over the remaining data axes.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..sharding import sites
from .sharded_moe import (compute_capacity, dropless_moe, expert_ffn,
                          load_balance_aux, moe_combine, moe_dispatch,
                          quantized_ep_moe, quantized_ep_ready, topk_gating)


def _constrain(x, spec, skip: bool = False):
    """Sharding constraint on the dispatch layout. ``skip`` during flax init,
    where trace shapes need not divide the mesh. Per-dimension, the constraint
    is dropped (→ replicated) when the dim doesn't divide its mesh axes — e.g.
    tiny inference batches over a large dp axis."""
    if skip:
        return x
    from ..parallel.topology import get_topology

    topo = get_topology()
    if topo.n_devices > 1:
        # inside shard_map (e.g. the SPMD pipeline body) the mesh axes are
        # manual: per-shard values carry no global sharding to constrain —
        # layout is already fixed by the enclosing in_specs
        manual = jax.sharding.get_abstract_mesh().manual_axes
        axes_in_spec = {a for entry in spec if entry is not None
                        for a in (entry if isinstance(entry, tuple) else (entry,))}
        if axes_in_spec & set(manual):
            return x
        eff = topo.filter_spec(spec, x.shape)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(topo.mesh, eff))
    return x


class MoEBlock(nn.Module):
    """Drop-in MLP replacement returning ``(out, aux_loss)``.

    The per-expert token counts diagnostic (reference ``MoE.forward``'s third
    return, ``exp_counts``) is sown as the ``moe_exp_counts`` intermediate:
    PRE-capacity router assignments with padding tokens excluded — matching
    the reference (``top1gating`` computes exp_counts from ``mask1`` before
    the capacity truncation) and identical semantics on both the capacity
    and dropless paths.

    ``used_token [G,S]`` (reference ``MoE.forward(hidden, used_token)``,
    ``moe/layer.py:115``) excludes padding tokens from dispatch + aux loss.
    Gating stochasticity (RSample / Jitter noise, Random Token Selection)
    draws from the ``"gating"`` rng collection when the caller provides one
    (``model.apply(..., rngs={"gating": key})``); without it gating is
    deterministic — eval and tracing stay reproducible.
    """
    cfg: object  # TransformerConfig

    def _sow_exp_counts(self, gates, k, e, used_token):
        """Pre-drop per-expert assignment counts (see class docstring)."""
        _, top_e = jax.lax.top_k(gates, k)                   # [G, S, k]
        hot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)      # [G, S, k, E]
        if used_token is not None:
            hot = hot * used_token.astype(jnp.int32)[..., None, None]
        self.sow("intermediates", "moe_exp_counts",
                 jnp.sum(hot, axis=(0, 1, 2)))

    @nn.compact
    def __call__(self, x, used_token=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        g, s, d = x.shape
        e, k = cfg.num_experts, cfg.moe_top_k
        f = cfg.moe_intermediate_size or cfg.intermediate_size
        drop_tokens = getattr(cfg, "moe_drop_tokens", True)
        if drop_tokens:
            capacity = compute_capacity(k, s, e, cfg.moe_capacity_factor)
        else:
            # static no-drop bound (the reference grows capacity dynamically,
            # sharded_moe.py:214 — a data-dependent shape XLA can't trace;
            # k*S is its worst case. moe_dropless is the efficient no-drop.)
            capacity = k * s
        gate_rng = (self.make_rng("gating")
                    if not self.is_initializing() and self.has_rng("gating") else None)
        noisy = getattr(cfg, "moe_noisy_gate_policy", None)

        # router in fp32 (reference TopKGate keeps the gate fp32)
        router = nn.Dense(e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")
        x_router = x.astype(jnp.float32)
        if noisy == "Jitter" and gate_rng is not None:
            # reference TopKGate jitters the router INPUT (sharded_moe.py:431)
            jit_rng, gate_rng = jax.random.split(gate_rng)
            x_router = x_router * jax.random.uniform(
                jit_rng, x_router.shape, minval=0.99, maxval=1.01)
        logits = router(x_router)

        init = nn.initializers.lecun_normal()
        swiglu = cfg.activation == "swiglu"
        # gate projection exists only for gated activations (mirrors MLP)
        w_gate = (self.param("expert_gate_proj", init, (e, d, f), jnp.float32)
                  if swiglu else None)
        w_up = self.param("expert_up_proj", init, (e, d, f), jnp.float32)
        w_down = self.param("expert_down_proj", init, (e, f, d), jnp.float32)
        # expert biases (megatron-MoE ParallelMLP experts carry them; the
        # llama-family MoEs do not) follow the dense-MLP bias heuristic
        zeros = nn.initializers.zeros
        b_up = (self.param("expert_up_bias", zeros, (e, f), jnp.float32)
                if cfg.ffn_bias else None)
        b_down = (self.param("expert_down_bias", zeros, (e, d), jnp.float32)
                  if cfg.ffn_bias else None)
        b_gate = (self.param("expert_gate_bias", zeros, (e, f), jnp.float32)
                  if cfg.ffn_bias and swiglu else None)
        skip = self.is_initializing()

        norm_topk = cfg.moe_norm_topk

        # qwen2_moe always-on shared expert, modulated by a sigmoid gate
        fs = cfg.moe_shared_expert_size
        if fs:
            sg = self.param("shared_gate_proj", init, (d, fs), jnp.float32)
            su = self.param("shared_up_proj", init, (d, fs), jnp.float32)
            sdn = self.param("shared_down_proj", init, (fs, d), jnp.float32)
            srt = self.param("shared_router", init, (d, 1), jnp.float32)

        # PR-MoE residual (reference MoE.forward, moe/layer.py:124): a dense
        # MLP runs beside the experts; a learned per-token 2-way softmax
        # coefficient blends them. Distinct from qwen2's shared expert
        # (sigmoid-modulated ADDITION) below.
        use_residual = getattr(cfg, "moe_use_residual", False)
        if use_residual:
            r_up = self.param("residual_up_proj", init, (d, f), jnp.float32)
            r_down = self.param("residual_down_proj", init, (f, d), jnp.float32)
            r_gate = (self.param("residual_gate_proj", init, (d, f), jnp.float32)
                      if swiglu else None)
            r_coef = self.param("residual_coefficient", init, (d, 2), jnp.float32)

        def add_residual(y):
            if not use_residual:
                return y
            if swiglu:
                h_r = nn.silu(x @ r_gate.astype(x.dtype)) * (x @ r_up.astype(x.dtype))
            else:
                h_r = nn.gelu(x @ r_up.astype(x.dtype))
            out_r = h_r @ r_down.astype(x.dtype)
            coef = nn.softmax((x.astype(jnp.float32) @ r_coef), axis=-1)
            coef = coef.astype(y.dtype)
            return y * coef[..., 0:1] + out_r * coef[..., 1:2]

        def add_shared(y):
            y = add_residual(y)
            if not fs:
                return y
            h_s = nn.silu(x @ sg.astype(x.dtype)) * (x @ su.astype(x.dtype))
            out_s = h_s @ sdn.astype(x.dtype)
            mod = nn.sigmoid((x.astype(jnp.float32) @ srt)).astype(x.dtype)
            return y + out_s * mod

        if getattr(cfg, "moe_dropless", False):
            # grouped-GEMM dropless path (reference cutlass moe_gemm /
            # megablocks): no capacity, no zero-padded compute. Token
            # grouping is a global sort under SPMD, so this path shines for
            # ep=1 (local groups); with ep>1 prefer the capacity einsums.
            gates = jax.nn.softmax(logits, axis=-1)
            aux = load_balance_aux(gates, used_token)
            self._sow_exp_counts(gates, k, e, used_token)
            y = dropless_moe(x, gates, k, w_gate, w_up, w_down,
                             activation=cfg.activation, norm_topk=norm_topk,
                             b_up=b_up, b_down=b_down, b_gate=b_gate)
            if used_token is not None:  # padding tokens contribute nothing
                y = y * used_token.astype(y.dtype)[..., None]
            y = add_shared(y.astype(x.dtype))
            y = _constrain(y, sites.moe_batch_act(3), skip)
            return y.astype(x.dtype), aux * cfg.moe_aux_loss_weight

        dispatch, combine, aux = topk_gating(
            logits, k, capacity, rng=gate_rng,
            noisy_gate_policy=noisy if noisy == "RSample" else None,
            drop_tokens=drop_tokens, norm_topk=norm_topk,
            used_token=used_token,
            use_rts=getattr(cfg, "moe_use_rts", True))
        # keep the token-major mask sharded like the activations (G over
        # dp, S over sp): leaving it unconstrained made the partitioner
        # replicate-and-repartition the dispatch collective-permute
        # ("involuntary full rematerialization", spmd_partitioner.cc:652)
        tok_mask_spec = sites.moe_batch_act(4, sp_axis="sp")
        dispatch = _constrain(dispatch, tok_mask_spec, skip)
        combine = _constrain(combine, tok_mask_spec, skip)

        self._sow_exp_counts(jax.nn.softmax(logits, axis=-1), k, e, used_token)

        if not skip and quantized_ep_ready(e, g, site_shape=(e, g, capacity, d),
                                           site_dtype=x.dtype):
            # compressed_collectives / comm-planner MoE site: the EP
            # dispatch/combine exchange runs explicitly with int8 payloads
            # (sharded_moe.py quantized_ep_moe) instead of the partitioner's
            # exact a2a
            y = quantized_ep_moe(
                x, dispatch, combine, w_up, w_down, w_gate=w_gate,
                b_up=b_up, b_down=b_down, b_gate=b_gate,
                activation=cfg.activation)
        else:
            # expert-major dispatch: [E, G, C, D], experts over the ep axis
            expert_in = moe_dispatch(x, dispatch)
            expert_in = _constrain(expert_in, sites.moe_expert_major_act(4), skip)
            out = expert_ffn(expert_in, w_up, w_down, w_gate=w_gate,
                             b_up=b_up, b_down=b_down, b_gate=b_gate,
                             activation=cfg.activation)
            out = _constrain(out, sites.moe_expert_major_act(4), skip)

            y = moe_combine(out, combine)
        y = add_shared(y.astype(x.dtype))
        y = _constrain(y, sites.moe_batch_act(3, sp_axis="sp"), skip)
        return y.astype(x.dtype), aux * cfg.moe_aux_loss_weight
