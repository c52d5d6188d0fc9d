"""Ragged-batch transformer forward over a paged KV cache.

Reference: the FastGen model implementations + ragged kernels
(``inference/v2/model_implementations/*``, ``kernels/ragged_ops/*`` —
blocked_flash, blocked_kv_rotary, logits_gather, atom_builder). TPU-native
re-design: instead of per-kernel CUDA ops, ONE jitted function processes the
packed token buffer —

* dense projections run over the flat ``[T]`` token buffer (MXU-friendly:
  every scheduled token, prompt chunk or decode, shares the same matmuls —
  this is the Dynamic SplitFuse property);
* per-sequence grouping is a static-shape gather ``[S, Q]``;
* KV pages are written a page at a time (``_kv_write``) and gathered with
  the trash-block convention (a slot with nothing to write rewrites page 0,
  never read);
* paged attention = grouped-GQA einsum over gathered pages with an
  absolute-position mask.

Operates directly on ``models.transformer.TransformerLM`` parameter pytrees
(same checkpoint loads serve v1 and v2 engines).
"""

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import (TransformerConfig, alibi_slopes,
                                   apply_activation, apply_rope,
                                   merge_partial_attention as merge_attention,
                                   rope_table)
from ...ops.pallas.paged_attention import NEG_INF, paged_flash_decode
from ...ops.pallas.paged_attention import paged_attention as paged_attention_pallas
from ...ops.pallas.quant import dequantize_rows, quantize_rows


# ---------------------------------------------------------------------------
# KV pool forms. A pool argument is either a plain array
# [L, N, Hk, bs, D] or, for int8 storage (kv_cache_dtype="int8"), a
# (values int8, scales fp32 [L, N, Hk, bs]) tuple — quantize-on-scatter,
# dequantize-on-gather with the quant.py row convention. The tuple form is
# only served by the gather (einsum) attention path; the engine forbids it
# for attn_backend="pallas".
# ---------------------------------------------------------------------------


def _pool_values(pool):
    return pool[0] if isinstance(pool, tuple) else pool


def _log_pool(op: str, nbytes: int) -> None:
    """Trace-time ledger entry for pool bytes an attention path touches per
    step: ``paged_pool_gather`` is the einsum path's materialized gathered
    copy (the tensor the Pallas decode kernel deletes), ``paged_pool_read``
    the kernel's in-place page-read upper bound (clamped trailing pages
    elide their DMA, so the true figure is the live-page subset). The ``pd``
    bench rung reads these rows."""
    from ... import comm

    comm.log_local(op, int(nbytes))


def _kv_layer(pool, i):
    """Layer ``i``'s view of a pool argument, preserving its form."""
    if isinstance(pool, tuple):
        return (pool[0][i], pool[1][i])
    return pool[i]


def _write_plan(block_table, pos, valid, bs: int):
    """Page-granular write plan for one step's new KV rows.

    Each sequence's new rows sit at contiguous positions (the SplitFuse
    packing invariant; the fused-decode window is contiguous by
    construction), so ``Q`` rows touch at most ``P = ceil((Q-1)/bs) + 1``
    consecutive logical pages, starting at the page of the first row.
    Returns ``(phys [S, P], row [S, Q])``: those pages' physical ids (the
    trash page 0 where the sequence writes nothing or its table ends) and
    each row's index inside the sequence's ``P * bs``-row span (``P * bs``,
    out of range and so dropped, for invalid rows)."""
    Q = pos.shape[1]
    B = block_table.shape[1]
    P = -(-(Q - 1) // bs) + 1
    writes = jnp.any(valid, axis=1)                                 # [S]
    first = jnp.min(jnp.where(valid, pos, jnp.iinfo(jnp.int32).max), axis=1)
    lp0 = jnp.where(writes, first // bs, 0)
    lp = lp0[:, None] + jnp.arange(P, dtype=jnp.int32)[None]        # [S, P]
    phys = jnp.take_along_axis(block_table, jnp.minimum(lp, B - 1), axis=1)
    phys = jnp.where(writes[:, None] & (lp < B), phys, 0)
    row = jnp.where(valid, pos - lp0[:, None] * bs, P * bs)
    return phys, row


def _page_rmw(arr, i, phys, row, src):
    """Read-modify-write layer ``i``'s pages ``phys`` of ``arr``
    ``[L, N, Hk, bs, *tail]`` with rows ``src [S, Q, Hk, *tail]``.

    Whole pages are gathered, patched while small, and scattered back along
    the major (layer, page) axes, which XLA:TPU applies to the donated pool
    in place. A row-granular scatter (``arr.at[i, blk, :, slot]``) indexes
    inside the tiled minor dims instead, and the compiler then relayouts the
    WHOLE pool around it — temporaries of several times the pool's size,
    which no pool of a useful size survives."""
    S, P = phys.shape
    hk, bs = arr.shape[2], arr.shape[3]
    tail = arr.shape[4:]
    old = arr[i, phys]                                  # [S, P, Hk, bs, *tail]
    tok = jnp.moveaxis(old, 3, 2).reshape(S, P * bs, hk, *tail)
    tok = tok.at[jnp.arange(S)[:, None], row].set(src.astype(arr.dtype),
                                                  mode="drop")
    new = jnp.moveaxis(tok.reshape(S, P, bs, hk, *tail), 2, 3)
    return arr.at[i, phys].set(new)


def _kv_write(pool, i, phys, row, vals):
    """Write new KV rows ``vals`` [S, Q, Hk, D] into layer ``i``'s pages
    (``phys``/``row`` from :func:`_write_plan`)."""
    if isinstance(pool, tuple):
        q, s = pool
        qv, sv = quantize_rows(vals)
        return (_page_rmw(q, i, phys, row, qv), _page_rmw(s, i, phys, row, sv))
    return _page_rmw(pool, i, phys, row, vals)


def _gather_pages(pool, block_table, dtype):
    """Gather a (possibly layer-sliced) pool's pages: [S, B, Hk, bs, D].
    Quantized pools dequantize on the gather; plain pools keep their storage
    dtype (consumers cast at the einsum)."""
    if isinstance(pool, tuple):
        q, s = pool
        out = dequantize_rows(q[block_table], s[block_table], dtype)
    else:
        out = pool[block_table]
    _log_pool("paged_pool_gather",
              int(np.prod(out.shape)) * jnp.dtype(out.dtype).itemsize)
    return out


def _pool_read_bytes(pool, block_table) -> int:
    """Per-step upper bound on the bytes the Pallas paged kernel can DMA for
    one pool: every block-table page at storage width (+ the scale rows for
    int8 pools) — never a materialized copy."""
    vals = _pool_values(pool)
    hk, bs, d = vals.shape[-3:]
    pages = int(np.prod(block_table.shape))
    n = pages * hk * bs * d * jnp.dtype(vals.dtype).itemsize
    if isinstance(pool, tuple):
        n += pages * hk * bs * 4  # fp32 per-row scales
    return n


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale
    return out if bias is None else out + bias


def _norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return _rms_norm(x, p["scale"], cfg.norm_eps)
    return _layer_norm(x, p["scale"], p.get("bias"), cfg.norm_eps)  # mpt: no bias


def _dense(p, x):
    """flax DenseGeneral kernels: [in, ...out]; optional bias."""
    k = p["kernel"]
    out = jnp.einsum("ti,i...->t...", x, k.astype(x.dtype))
    if "bias" in p:
        out = out + p["bias"].astype(x.dtype)
    return out


def _qkv(cfg, ap, y, rope_cs, positions):
    """Shared q/k/v projection + rotary for the packed and decode paths."""
    qt = _dense(ap["q_proj"], y)                # [T, Hq, D]
    kt = _dense(ap["k_proj"], y)                # [T, Hk, D]
    vt = _dense(ap["v_proj"], y)
    if cfg.position == "rope":
        cos, sin = rope_cs
        il = cfg.rotary_interleaved
        qt = _rope(qt, cos, sin, positions, il)
        kt = _rope(kt, cos, sin, positions, il)
    return qt, kt, vt


def _moe_mlp(cfg, lp, y):
    """MoE block over a flat token buffer [T, D] (reference FastGen MoE
    models: mixtral / qwen2_moe via ``moe_scatter``/``moe_gather`` +
    cutlass ``moe_gemm``). Serving uses the dropless grouped-GEMM path —
    exact dense routing, no capacity drops."""
    from ...moe.sharded_moe import dropless_moe

    logits = y.astype(jnp.float32) @ lp["router"]["kernel"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    out = dropless_moe(y[None], gates[None], cfg.moe_top_k,
                       lp.get("expert_gate_proj"), lp["expert_up_proj"],
                       lp["expert_down_proj"], activation=cfg.activation,
                       norm_topk=cfg.moe_norm_topk,
                       b_up=lp.get("expert_up_bias"),
                       b_down=lp.get("expert_down_bias"),
                       b_gate=lp.get("expert_gate_bias"))[0]
    out = out.astype(y.dtype)
    if "shared_gate_proj" in lp:  # qwen2_moe always-on shared expert
        h = (jax.nn.silu(y @ lp["shared_gate_proj"].astype(y.dtype))
             * (y @ lp["shared_up_proj"].astype(y.dtype)))
        mod = jax.nn.sigmoid(
            y.astype(jnp.float32) @ lp["shared_router"].astype(jnp.float32))
        out = out + (h @ lp["shared_down_proj"].astype(y.dtype)) * mod.astype(y.dtype)
    return out


def _ffn(cfg, lp, y):
    """Dense MLP or MoE, by layer params."""
    if "moe" in lp:
        return _moe_mlp(cfg, lp["moe"], y)
    return _mlp(cfg, lp["mlp"], y)


def _mlp(cfg, mp, y):
    if cfg.activation == "swiglu":
        hid = jax.nn.silu(_dense(mp["gate_proj"], y)) * _dense(mp["up_proj"], y)
    else:
        hid = apply_activation(cfg.activation, _dense(mp["up_proj"], y))
    return _dense(mp["down_proj"], hid)


def _lm_logits(cfg, params, h_sel):
    h_sel = h_sel.astype(jnp.float32)
    if cfg.tie_embeddings:
        return h_sel @ params["embed"]["embedding"].astype(jnp.float32).T
    logits = h_sel @ params["lm_head"]["kernel"].astype(jnp.float32)
    if cfg.lm_head_bias:  # gpt-j / phi
        logits = logits + params["lm_head"]["bias"].astype(jnp.float32)
    return logits


def _rope(x, cos, sin, positions, interleaved=False):
    """x: [T, H, D]; positions: [T] — the shared rotary
    (models.transformer.apply_rope, incl. partial rotary and the gpt-j
    rotate-every-two pairing) over a flat token buffer, batch of one."""
    return apply_rope(x[None], cos, sin, positions[None],
                      interleaved=interleaved)[0]


def paged_attention(qg, k_pool, v_pool, block_table, positions_g, q_valid,
                    kv_len, return_stats: bool = False, alibi=None,
                    alibi_post_scale: bool = False, scale=None, window=None):
    """Grouped paged attention.

    qg: [S, Q, Hq, D] grouped queries; k/v_pool: [N, Hk, bs, D] this layer's
    pages (head-major); block_table: [S, B]; positions_g: [S, Q] absolute
    positions; q_valid: [S, Q] bool; kv_len: [S]. Returns [S, Q, Hq, D].
    Slot j of sequence s attends iff j <= position of the query (also masks
    unwritten/trash slots because kv_len bounds writes). With
    ``return_stats`` also returns the softmax ``(m, l)`` per row
    ([S, Q, Hq] fp32) for two-source merges.

    Family knobs (mirroring ``models.transformer.attention_core``): ``alibi``
    per-head slopes [Hq] subtract ``slope * (q_pos - k_pos)`` from the
    logits — the gathered slot index IS the key's absolute position, so the
    distance is exact under paging; ``alibi_post_scale`` adds the raw slope
    after scaling (mpt) instead of folding the 1/sqrt(d) in (falcon/bloom);
    ``scale`` overrides 1/sqrt(d) (gpt-neo trains unscaled); ``window``
    masks keys at distance >= window (gpt-neo local layers).
    """
    s, q, hq, d = qg.shape
    hk = _pool_values(k_pool).shape[1]
    bs = _pool_values(k_pool).shape[2]
    rep = hq // hk
    # gather pages [S, B, Hk, bs, D] -> slot-major [S, B*bs, Hk, D]
    # (int8 pools dequantize on this gather)
    kg = _gather_pages(k_pool, block_table, qg.dtype)
    vg = _gather_pages(v_pool, block_table, qg.dtype)
    kg = kg.transpose(0, 1, 3, 2, 4).reshape(s, -1, hk, d)
    vg = vg.transpose(0, 1, 3, 2, 4).reshape(s, -1, hk, d)
    m = kg.shape[1]
    qq = qg.reshape(s, q, hk, rep, d)
    scale = (1.0 / np.sqrt(d)) if scale is None else float(scale)
    logits = jnp.einsum("sqhrd,skhd->shrqk", qq, kg.astype(qg.dtype),
                        preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(m)[None, None, None, None, :]
    pos_q = positions_g[:, None, None, :, None]
    if alibi is not None:
        sl_factor = 1.0 if alibi_post_scale else scale
        sl = (sl_factor * jnp.asarray(alibi, jnp.float32)).reshape(hk, rep)
        dist = (pos_q - slot).astype(jnp.float32)          # [s,1,1,q,m]
        logits = logits - sl[None, :, :, None, None] * dist
    valid = (slot <= pos_q) & q_valid[:, None, None, :, None]
    valid = valid & (slot < kv_len[:, None, None, None, None])
    if window is not None:
        valid = valid & (pos_q - slot < window)
    logits = jnp.where(valid, logits, NEG_INF)
    m_row = jnp.max(logits, axis=-1)                       # [s,hk,rep,q]
    p = jnp.where(valid, jnp.exp(logits - m_row[..., None]), 0.0)
    l_row = jnp.sum(p, axis=-1)
    acc = jnp.einsum("shrqk,skhd->sqhrd", p.astype(qg.dtype),
                     vg.astype(qg.dtype), preferred_element_type=jnp.float32)
    safe_l = jnp.where(l_row == 0.0, 1.0, l_row)
    out = (acc / jnp.transpose(safe_l, (0, 3, 1, 2))[..., None]).astype(qg.dtype)
    out = out.reshape(s, q, hq, d)
    if return_stats:
        stats = lambda a: jnp.transpose(a, (0, 3, 1, 2)).reshape(s, q, hq)
        return out, stats(m_row), stats(l_row)
    return out


def _ragged_hidden(params, cfg: TransformerConfig, kv_k, kv_v, tokens,
                   positions, gather_idx, block_table, kv_len,
                   start_pos, chunk_len, attn_impl: str
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The packed ragged forward up to the final norm: returns the
    final-norm hidden states for EVERY packed token (``x [T, H]``) plus the
    updated pools. ``_ragged_forward_impl`` selects per-sequence sample
    positions on top; ``verify_step`` reads all T rows (speculative-decode
    verification needs logits at every draft position).

    kv pools: [L, N, Hk, bs, D] (donated — updated in place).
    ``attn_impl``: "einsum" (dense gathered-page reference path) or "pallas"
    (paged online-softmax kernel, ops/pallas/paged_attention.py).
    """
    T = tokens.shape[0]
    S, Q = gather_idx.shape
    bs = _pool_values(kv_k).shape[3]
    dtype = cfg.dtype

    x = params["embed"]["embedding"].astype(dtype)[tokens]          # [T, H]
    if cfg.embed_norm:  # bloom word_embeddings_layernorm
        x = _norm(cfg, params["embed_norm"], x)
    if cfg.position == "learned":
        # OPT embeds positions shifted by pos_offset (2)
        x = x + params["pos_embed"][positions + cfg.pos_offset].astype(dtype)
    if cfg.position == "rope":
        cos, sin = rope_table(cfg.max_seq_len, cfg.rotary_dim, cfg.rope_theta)
    alibi = (jnp.asarray(alibi_slopes(cfg.num_heads,
                                      bf16_round=not cfg.alibi_post_scale))
             if cfg.position == "alibi" else None)

    q_valid = gather_idx < T                                        # [S, Q]
    safe_gather = jnp.minimum(gather_idx, T - 1)
    pos_g = jnp.where(q_valid, positions[safe_gather], 0)           # [S, Q]
    w_phys, w_row = _write_plan(block_table, pos_g, q_valid, bs)

    h, hk, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    rope_cs = (cos, sin) if cfg.position == "rope" else None
    for i in range(cfg.num_layers):
        lp = params[f"layer_{i}"]
        y = _norm(cfg, lp["attn_norm"], x)
        ap = lp["attn"]
        qt, kt, vt = _qkv(cfg, ap, y, rope_cs, positions)
        # group per sequence (extra zero pad row at index T)
        qg = jnp.concatenate([qt, jnp.zeros_like(qt[:1])])[gather_idx]
        kg = jnp.concatenate([kt, jnp.zeros_like(kt[:1])])[gather_idx]
        vg = jnp.concatenate([vt, jnp.zeros_like(vt[:1])])[gather_idx]
        kv_k = _kv_write(kv_k, i, w_phys, w_row, kg)
        kv_v = _kv_write(kv_v, i, w_phys, w_row, vg)
        if attn_impl == "pallas":
            if isinstance(kv_k, tuple):
                raise ValueError(
                    "the packed-step pallas kernel takes compute-dtype "
                    "pools; quantized pools run the einsum gather here "
                    "(the fused-dequant kernel serves decode_loop)")
            _log_pool("paged_pool_read",
                      _pool_read_bytes(kv_k, block_table)
                      + _pool_read_bytes(kv_v, block_table))
            out = paged_attention_pallas(qg, kv_k[i], kv_v[i], block_table,
                                         start_pos, chunk_len, kv_len,
                                         sm_scale=cfg.attn_scale)
        else:
            win = cfg.layer_windows[i] if cfg.layer_windows else None
            out = paged_attention(qg, _kv_layer(kv_k, i), _kv_layer(kv_v, i),
                                  block_table, pos_g,
                                  q_valid, kv_len, alibi=alibi,
                                  alibi_post_scale=cfg.alibi_post_scale,
                                  scale=cfg.attn_scale,
                                  window=win)                       # [S, Q, Hq, D]
        # ungroup back to the flat token buffer ([T+1] with pad row dropped)
        flat = jnp.zeros((T + 1, h, d), out.dtype)
        flat = flat.at[gather_idx.reshape(-1)].set(out.reshape(-1, h, d))
        attn_tok = flat[:T]
        attn_out = _dense_multi_in(ap["o_proj"], attn_tok)          # [T, H]
        if cfg.parallel_residual:
            # falcon / gpt-j / phi: attn and mlp both branch off x
            y_mlp = (y if cfg.parallel_shared_norm
                     else _norm(cfg, lp["mlp_norm"], x))
            x = x + attn_out + _ffn(cfg, lp, y_mlp)
        else:
            x = x + attn_out
            x = x + _ffn(cfg, lp, _norm(cfg, lp["mlp_norm"], x))

    x = _norm(cfg, params["final_norm"], x)
    return x, kv_k, kv_v


def _ragged_forward_impl(params, cfg: TransformerConfig, kv_k, kv_v, tokens,
                         positions, gather_idx, block_table, kv_len,
                         logits_idx, start_pos, chunk_len, attn_impl: str
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One engine step over a packed ragged batch. Returns (logits [S, V]
    fp32 at each sequence's logits_idx token, new kv_k, kv_v)."""
    x, kv_k, kv_v = _ragged_hidden(params, cfg, kv_k, kv_v, tokens, positions,
                                   gather_idx, block_table, kv_len,
                                   start_pos, chunk_len, attn_impl)
    # logits only at the sample positions (reference logits_gather kernel);
    # logits_idx == T selects the zero pad row for non-sampling slots
    h_sel = jnp.concatenate([x, jnp.zeros_like(x[:1])])[logits_idx]  # [S, H]
    logits = _lm_logits(cfg, params, h_sel)
    return logits, kv_k, kv_v


@partial(jax.jit, static_argnames=("cfg", "attn_impl"),
         donate_argnames=("kv_k", "kv_v"))
def ragged_forward(params, cfg: TransformerConfig, kv_k, kv_v, tokens,
                   positions, gather_idx, block_table, kv_len, logits_idx,
                   start_pos=None, chunk_len=None, attn_impl: str = "einsum"
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Jitted ragged step returning full logits (see _ragged_forward_impl)."""
    if start_pos is None:
        if attn_impl == "pallas":
            raise ValueError("attn_impl='pallas' requires start_pos/chunk_len "
                             "(the contiguous-chunk invariant); only the "
                             "einsum path can derive masks from gather_idx")
        start_pos = kv_len  # unused by the einsum path
        chunk_len = kv_len
    return _ragged_forward_impl(params, cfg, kv_k, kv_v, tokens, positions,
                                gather_idx, block_table, kv_len, logits_idx,
                                start_pos, chunk_len, attn_impl)


@partial(jax.jit, static_argnames=("cfg", "attn_impl", "greedy"),
         donate_argnames=("kv_k", "kv_v"))
def ragged_step(params, cfg: TransformerConfig, kv_k, kv_v, tokens, positions,
                gather_idx, block_table, kv_len, logits_idx, start_pos,
                chunk_len, key, temperature, attn_impl: str = "einsum",
                greedy: bool = True
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Jitted ragged step with ON-DEVICE sampling.

    The reference engine gathers logits to host and samples in Python per
    step (and so does our v1 path); here sampling stays in the compiled
    program (reference ``logits_gather`` + host sampler collapsed into the
    step) and only ``[S]`` int32 tokens cross to host.
    """
    logits, kv_k, kv_v = _ragged_forward_impl(
        params, cfg, kv_k, kv_v, tokens, positions, gather_idx, block_table,
        kv_len, logits_idx, start_pos, chunk_len, attn_impl)
    if greedy:
        toks = jnp.argmax(logits, axis=-1)
    else:
        toks = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6), axis=-1)
    return toks.astype(jnp.int32), kv_k, kv_v


@partial(jax.jit, static_argnames=("cfg", "attn_impl"),
         donate_argnames=("kv_k", "kv_v"))
def verify_step(params, cfg: TransformerConfig, kv_k, kv_v, tokens, positions,
                gather_idx, block_table, kv_len, start_pos, chunk_len,
                attn_impl: str = "einsum"
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative-decode verification: the packed ragged forward with a
    greedy argmax at EVERY packed token position, not just logits_idx.

    Each sequence's chunk is ``[last committed token, draft_1..draft_k]``;
    row ``t`` of the returned ``[T] int32`` is the model's next-token
    prediction AFTER input token ``t`` — the host accepts the longest draft
    prefix where ``draft_{i+1} == next[i]`` and commits ``next[j]`` at the
    first mismatch, which is by construction exactly the sequential greedy
    stream. KV rows for all k+1 inputs are scattered as usual; the engine
    rewinds ``seen_tokens`` past the rejected suffix and those rows are
    rewritten when their positions are next reached (reads never see them:
    attention masks by kv_len/pool_len = committed length).
    """
    x, kv_k, kv_v = _ragged_hidden(params, cfg, kv_k, kv_v, tokens, positions,
                                   gather_idx, block_table, kv_len,
                                   start_pos, chunk_len, attn_impl)
    logits = _lm_logits(cfg, params, x)                           # [T, V]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv_k, kv_v


def _dense_multi_in(p, x):
    """o_proj DenseGeneral with axis=(-2,-1): kernel [H, D, hidden]."""
    out = jnp.einsum("thd,hdo->to", x, p["kernel"].astype(x.dtype))
    if "bias" in p:
        out = out + p["bias"].astype(x.dtype)
    return out


@partial(jax.jit, static_argnames=("cfg", "n_steps", "attn_impl", "greedy"),
         donate_argnames=("kv_k", "kv_v"))
def decode_loop(params, cfg: TransformerConfig, kv_k, kv_v, tokens0, pos0,
                block_table, active, key, temperature, n_steps: int = 16,
                attn_impl: str = "einsum", greedy: bool = True
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``n_steps`` fused decode iterations in ONE compiled program.

    The reference serving loop (and our ``step()``) round-trips host every
    token: logits→sample→repack. This runs the whole
    forward→sample→KV-append loop as a ``lax.scan`` on device instead and
    ships back only ``[S, n_steps]`` int32.

    The KV pool is FROZEN during the scan. XLA (at least on this backend)
    copies a scanned carry on every iteration when it is updated by
    scatter/DUS, so carrying the multi-GB pool made step time proportional
    to POOL size (measured: ~1.1 ms/step per 0.9 GB — dominating decode).
    Instead the scan carries only a small in-window KV buffer
    ``[L, n_steps, S, Hk, D]``; each step attends to the frozen pool (paged
    kernel, ``return_stats``) and to the window (dense, masked), merging the
    two with the flash combine algebra; the window is scattered into the
    pool ONCE after the scan.

    tokens0: [S] last sampled token per sequence; pos0: [S] its absolute
    position (== tokens cached so far); block_table [S, B] must already cover
    ``pos0 + n_steps`` (reserve before calling); active: [S] bool (inactive
    slots write to the trash block). Returns (tokens [S, n_steps], kv pools).
    """
    S = tokens0.shape[0]
    bs = _pool_values(kv_k).shape[3]
    L, Hq, Hk, D = cfg.num_layers, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    G = Hq // Hk
    W = n_steps
    dtype = cfg.dtype
    sm = (1.0 / np.sqrt(D)) if cfg.attn_scale is None else float(cfg.attn_scale)
    if cfg.position == "rope":
        cos, sin = rope_table(cfg.max_seq_len, cfg.rotary_dim, cfg.rope_theta)
    alibi = (jnp.asarray(alibi_slopes(Hq, bf16_round=not cfg.alibi_post_scale))
             if cfg.position == "alibi" else None)
    alibi_sl = (None if alibi is None else
                ((1.0 if cfg.alibi_post_scale else sm)
                 * alibi.astype(jnp.float32)).reshape(Hk, G))
    ones = jnp.ones((S,), jnp.int32)
    pool_len = pos0  # tokens cached before this call — static for the scan
    rope_cs = (cos, sin) if cfg.position == "rope" else None

    def forward_one(wk, wv, toks, pos, t):
        x = params["embed"]["embedding"].astype(dtype)[toks]        # [S, H]
        if cfg.embed_norm:  # bloom word_embeddings_layernorm
            x = _norm(cfg, params["embed_norm"], x)
        if cfg.position == "learned":
            x = x + params["pos_embed"][pos + cfg.pos_offset].astype(dtype)
        widx = jnp.arange(W)
        wmask = widx <= t                                           # [W]
        for i in range(cfg.num_layers):
            lp = params[f"layer_{i}"]
            y = _norm(cfg, lp["attn_norm"], x)
            ap = lp["attn"]
            qt, kt, vt = _qkv(cfg, ap, y, rope_cs, pos)             # [S, H*, D]
            wk = jax.lax.dynamic_update_slice(
                wk, kt.astype(wk.dtype)[None, None], (i, t, 0, 0, 0))
            wv = jax.lax.dynamic_update_slice(
                wv, vt.astype(wv.dtype)[None, None], (i, t, 0, 0, 0))
            win = cfg.layer_windows[i] if cfg.layer_windows else None
            if attn_impl == "pallas":
                # resident-pool flash decode: the kernel indexes (layer,
                # page) through the block table, so neither a per-layer
                # pool slice nor a gathered copy materializes — int8 pools
                # ride as (values, scales) with the dequant fused in-kernel
                _log_pool("paged_pool_read",
                          _pool_read_bytes(kv_k, block_table)
                          + _pool_read_bytes(kv_v, block_table))
                o1, m1, l1 = paged_flash_decode(
                    qt, kv_k, kv_v, block_table, pos, pool_len,
                    layer=i, sm_scale=sm, return_stats=True)  # [S, Hq, *]
            else:
                qg = qt[:, None]                                # [S, 1, Hq, D]
                o1, m1, l1 = paged_attention(
                    qg, _kv_layer(kv_k, i), _kv_layer(kv_v, i), block_table,
                    pos[:, None],
                    active[:, None], pool_len, return_stats=True,
                    alibi=alibi, alibi_post_scale=cfg.alibi_post_scale,
                    scale=cfg.attn_scale, window=win)
                o1, m1, l1 = o1[:, 0], m1[:, 0], l1[:, 0]       # [S,Hq,*]

            # dense attention over the in-window tokens (incl. this one);
            # in-window token w sits at absolute position pos0 + w, so the
            # query (at pos0 + t) is at distance t - w from it for every
            # sequence — family bias/masking reuses that shared distance
            wki = jax.lax.dynamic_index_in_dim(wk, i, 0, keepdims=False)
            wvi = jax.lax.dynamic_index_in_dim(wv, i, 0, keepdims=False)
            qr = qt.reshape(S, Hk, G, D)
            lg2 = jnp.einsum("shgd,wshd->shgw", qr, wki.astype(qt.dtype),
                             preferred_element_type=jnp.float32) * sm
            wdist = (t - widx).astype(jnp.float32)                  # [W]
            if alibi_sl is not None:
                lg2 = lg2 - alibi_sl[None, :, :, None] * wdist[None, None, None]
            wmask_l = wmask if win is None else (wmask & (t - widx < win))
            lg2 = jnp.where(wmask_l[None, None, None], lg2, NEG_INF)
            m2 = jnp.max(lg2, axis=-1)                              # [S,Hk,G]
            p2 = jnp.where(wmask_l[None, None, None],
                           jnp.exp(lg2 - m2[..., None]), 0.0)
            l2 = jnp.sum(p2, axis=-1)
            acc2 = jnp.einsum("shgw,wshd->shgd", p2.astype(qt.dtype),
                              wvi.astype(qt.dtype),
                              preferred_element_type=jnp.float32)
            o2 = acc2 / jnp.where(l2 == 0.0, 1.0, l2)[..., None]

            merged = merge_attention(o1.reshape(S, Hk, G, D),
                                     m1.reshape(S, Hk, G), l1.reshape(S, Hk, G),
                                     o2, m2, l2)
            attn_tok = merged.reshape(S, Hq, D).astype(dtype)
            attn_out = _dense_multi_in(ap["o_proj"], attn_tok)
            if cfg.parallel_residual:
                y_mlp = (y if cfg.parallel_shared_norm
                         else _norm(cfg, lp["mlp_norm"], x))
                x = x + attn_out + _ffn(cfg, lp, y_mlp)
            else:
                x = x + attn_out
                x = x + _ffn(cfg, lp, _norm(cfg, lp["mlp_norm"], x))
        x = _norm(cfg, params["final_norm"], x)
        logits = _lm_logits(cfg, params, x)
        return logits, wk, wv

    def body(carry, t):
        wk, wv, toks, pos, key = carry
        logits, wk, wv = forward_one(wk, wv, toks, pos, t)
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits / jnp.maximum(temperature, 1e-6),
                axis=-1).astype(jnp.int32)
        return (wk, wv, nxt, pos + 1, key), nxt

    wk0 = jnp.zeros((L, W, S, Hk, D), dtype)
    wv0 = jnp.zeros((L, W, S, Hk, D), dtype)
    (wk, wv, *_), toks = jax.lax.scan(
        body, (wk0, wv0, tokens0, pos0, key), jnp.arange(n_steps))

    # one batched write of the whole window into the pool
    tpos = pos0[:, None] + jnp.arange(W, dtype=jnp.int32)[None]     # [S, W]
    w_phys, w_row = _write_plan(block_table, tpos,
                                jnp.broadcast_to(active[:, None], tpos.shape),
                                bs)
    wkt = wk.transpose(0, 2, 1, 3, 4)                               # [L,S,W,..]
    wvt = wv.transpose(0, 2, 1, 3, 4)
    for i in range(L):
        kv_k = _kv_write(kv_k, i, w_phys, w_row, wkt[i])
        kv_v = _kv_write(kv_v, i, w_phys, w_row, wvt[i])
    return toks.T, kv_k, kv_v                                       # [S, n_steps]


# ---------------------------------------------------------------------------
# TP-sharded decode projections (call inside shard_map over the tp axis).
#
# Decode TP layout: the S decode rows are sharded over the axis ([S/p, H]
# per rank) and the projection weights stay column-sharded ([H, n/p] — each
# rank keeps its head/vocab shard resident, nothing gathers weights). The
# per-step collective is then the tiny sequence-row gather, and
# ``impl="fused_matmul"`` hides it behind the projection matmul
# (``ops/collective_matmul.all_gather_matmul`` / ``matmul_reduce_scatter``)
# instead of paying it serially before the matmul — the T3
# compute/collective-fusion thesis applied to the decode hot loop. The KV
# pool shards by kv head alongside the projections, so each rank's paged
# attention covers every sequence over its own heads. ``resolve`` asks the
# collective planner (op=``gather_matmul``, consumer=``"decode"``) when the
# impl is left at ``"auto"``; the decision lands in the plan table, so the
# static auditor reconciles the decode-TP collectives against the plan
# instead of flagging them unplanned.
# ---------------------------------------------------------------------------


def resolve_decode_tp_impl(axis: str, shape, dtype) -> str:
    """``"fused_matmul" | "xla"`` for the decode projections' row gather:
    planner-resolved (knob > cache > cost model > microbench, recorded in
    the plan table) when a planner is active, the unfused XLA gather
    otherwise."""
    from ...comm.planner import planner_active, resolve_site

    if not planner_active():
        return "xla"
    try:
        d = resolve_site(op="gather_matmul", shape=tuple(int(s) for s in shape),
                         dtype=dtype, axes=(str(axis),), consumer="decode")
        return "fused_matmul" if d.impl == "fused_matmul" else "xla"
    except Exception:
        return "xla"


def tp_decode_matmul(x, w, axis: str, *, impl: str = "auto"):
    """Column-parallel decode projection: ``[S/p, H]`` local decode rows ×
    ``[H, n_local]`` resident weight shard → ``[S, n_local]`` (every
    sequence, this rank's output columns). ``fused_matmul`` rides
    :func:`~...ops.collective_matmul.all_gather_matmul` — the row-chunk ring
    hides behind the partial matmuls; ``xla`` gathers the rows first. Call
    inside ``shard_map``."""
    from ...ops.collective_matmul import all_gather_matmul

    if impl == "auto":
        impl = resolve_decode_tp_impl(axis, x.shape, x.dtype)
    if impl == "fused_matmul":
        return all_gather_matmul(x, w, axis)
    full = jax.lax.all_gather(x, axis, axis=0, tiled=True)
    return full @ w.astype(full.dtype)


def tp_decode_out_proj(attn, wo, axis: str, *, impl: str = "auto"):
    """Row-parallel decode output projection: ``[S, n_local]`` per-rank
    attention columns × ``[n_local, H]`` shard, summed over ranks and row-
    scattered back to ``[S/p, H]``. ``fused_matmul`` rides
    :func:`~...ops.collective_matmul.matmul_reduce_scatter` (reduction ring
    behind the chunked matmul; needs ``S % p == 0``). Call inside
    ``shard_map``."""
    from ...ops.collective_matmul import matmul_reduce_scatter

    if impl == "auto":
        impl = resolve_decode_tp_impl(axis, attn.shape, attn.dtype)
    if impl == "fused_matmul":
        return matmul_reduce_scatter(attn, wo, axis)
    return jax.lax.psum_scatter(attn @ wo.astype(attn.dtype), axis,
                                scatter_dimension=0, tiled=True)


def tp_decode_logits(h, w_vocab, axis: str, *, impl: str = "auto"):
    """Vocab-parallel LM head for decode: ``[S/p, H]`` local rows ×
    ``[H, V/p]`` vocab shard → ``[S, V/p]`` local-vocab logits for ALL
    sequences — the row gather (tiny) overlaps the head matmul under
    ``fused_matmul`` instead of preceding it. Pair with
    :func:`tp_greedy_token` to sample without ever gathering ``[S, V]``."""
    return tp_decode_matmul(h, w_vocab, axis, impl=impl)


def tp_greedy_token(local_logits, axis: str):
    """Global greedy argmax from vocab-sharded logits: each rank contributes
    its ``(best value, global token id)`` pair and only ``[S]``-sized
    scalars ride the wire instead of the vocab row. Tie-breaking matches the
    dense ``argmax`` (lowest global id wins: per-shard argmax picks the
    lowest local id, the cross-shard argmax picks the first = lowest-offset
    shard). Call inside ``shard_map``."""
    vloc = local_logits.shape[-1]
    off = jax.lax.axis_index(axis) * vloc
    loc = local_logits.astype(jnp.float32)
    best = jnp.max(loc, axis=-1)                                   # [S]
    idx = (jnp.argmax(loc, axis=-1) + off).astype(jnp.int32)
    bests = jax.lax.all_gather(best, axis, axis=0)                 # [p, S]
    idxs = jax.lax.all_gather(idx, axis, axis=0)
    win = jnp.argmax(bests, axis=0)                                # [S]
    return jnp.take_along_axis(idxs, win[None], axis=0)[0]
