"""Inference engine v2: continuous ragged batching (FastGen analogue).

Reference ``InferenceEngineV2`` (``inference/v2/engine_v2.py:30``):
``put(uids, tokens)`` admits work, each engine step packs prompt chunks +
decode tokens into one forward pass (Dynamic SplitFuse token budgeting,
blogs/deepspeed-fastgen/README.md:94-105), ``query``/``can_schedule`` expose
scheduling capacity. TPU-native: static-shape packed batches (one XLA program
for every batch mix), paged KV pools donated through the jitted step, host-side
scheduler/allocator.
"""

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import TransformerConfig, TransformerLM
from ...utils.logging import log_dist
from .model import decode_loop, ragged_step, verify_step
from .ragged.kv_cache import BlockedKVCache
from .ragged.prefix_index import ROOT_HASH, chain_hashes, hash_block
from .ragged.ragged_manager import DSStateManager
from .ragged.ragged_wrapper import RaggedBatch, RaggedBatchWrapper


@dataclass
class RaggedInferenceEngineConfig:
    """Knob vocabulary follows the reference's DSStateManagerConfig /
    RaggedInferenceEngineConfig."""
    token_budget: int = 256         # max tokens per engine step (T)
    max_ragged_sequence_count: int = 16   # sequence slots per step (S)
    max_chunk_size: int = 128       # SplitFuse prompt chunk cap (Q)
    num_kv_blocks: int = 512
    kv_block_size: int = 32
    max_blocks_per_seq: int = 64
    dtype: str = "float32"
    # KV pool storage dtype (reference FP-quantizer KV use case): e.g.
    # "float8_e4m3fn" halves KV memory vs bf16; None = the compute dtype.
    # Writers/readers already cast through the pool dtype, so this is purely
    # a storage-precision knob; the gather path dequantizes on read.
    # "int8" selects QUANTIZED storage instead of a cast: per-row absmax
    # scales ride alongside the pool (ops/pallas/quant.py quantize_rows),
    # writers quantize on scatter and the gather path dequantizes on read.
    kv_cache_dtype: Optional[str] = None
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    # "auto": Pallas paged kernel on TPU, einsum reference path on CPU.
    attn_backend: str = "auto"    # auto | pallas | einsum
    # fused-decode attention path (model.decode_loop) SPECIFICALLY: "auto"
    # resolves model field > this knob > attn_backend > planner (decode_attn
    # op) > accelerator heuristic, mirroring resolve_loss_impl. The pallas
    # decode kernel reads the resident pool in place (incl. int8 (values,
    # scales) pools, dequant fused in-kernel); structural fallbacks
    # (ALiBi / windows / fp8 storage / off-tile head dim on TPU) warn once
    # and run the gathered-page einsum reference instead.
    decode_attn_backend: str = "auto"   # auto | pallas | einsum
    # decode iterations fused into one compiled program by decode_batch()
    # (one host round-trip per chunk instead of per token)
    decode_chunk: int = 16
    # cap on the per-dispatch fused window: the frozen-pool decode carries an
    # in-window KV buffer [L, n, S, Hk, D] and runs an n-wide dense window
    # attention each step, so an unbounded n would grow HBM and O(n^2) work;
    # longer runs are chunked into windows of this size
    max_fused_window: int = 512
    # content-addressed prefix KV reuse (ragged/prefix_index.py): admission
    # matches the longest chain of registered full blocks over the prompt,
    # maps those pages shared (refcounted, COW on the one partial-tail
    # write), and prefills only the uncached tail. Off = bit-identical to
    # the pre-cache engine (no hashing, no refcount divergence).
    enable_prefix_cache: bool = False
    # n-gram speculative decoding (spec_decode_batch): draft up to k tokens
    # per live sequence from the most recent prior occurrence of the last
    # spec_ngram tokens in prompt+generated, verify all drafts in ONE
    # packed dispatch, commit the accepted prefix + the model's correction.
    # Greedy-only (the acceptance rule compares argmax streams, so the
    # committed tokens are bitwise the sequential greedy output). 0 = off.
    spec_decode_k: int = 0
    spec_ngram: int = 2


@dataclass
class ReuseStats:
    """Cumulative prefix-cache / speculative-decode counters (the serving
    tier samples these into ServingMetrics gauges)."""
    prefix_lookups: int = 0          # put() admissions that consulted the index
    prefix_hits: int = 0             # admissions that mapped >= 1 cached block
    prefix_tokens_reused: int = 0    # prompt tokens never re-prefilled
    prefix_blocks_shared: int = 0    # pages mapped shared (blocks saved)
    cow_forks: int = 0               # shared blocks copy-on-write-forked
    spec_steps: int = 0              # verify dispatches
    spec_drafted: int = 0            # draft tokens proposed
    spec_accepted: int = 0           # draft tokens accepted


_DECODE_WARNED = set()


def _warn_decode_once(msg: str) -> None:
    if msg in _DECODE_WARNED:
        return
    _DECODE_WARNED.add(msg)
    from ...utils.logging import logger

    logger.warning(msg)


class InferenceEngineV2:
    def __init__(self, model: TransformerLM, params,
                 config: Optional[RaggedInferenceEngineConfig] = None):
        self.config = config or RaggedInferenceEngineConfig()
        c = self.config
        self.model = model  # reference engine_v2 `model` property
        self.cfg: TransformerConfig = model.cfg
        # families whose attention needs per-head logit bias/windowing
        # beyond plain scaled causal (ALiBi bloom/mpt, windowed gpt-neo
        # local layers): served on the gathered-page einsum path — both
        # Pallas kernels take an explicit sm_scale, so attn_scale families
        # (unscaled gpt-neo globals) no longer count as special
        self._special_attn = (self.cfg.position == "alibi"
                              or self.cfg.layer_windows is not None)
        dtype = jnp.dtype(c.dtype)
        self.params = jax.tree.map(
            lambda x: jnp.asarray(x, dtype) if jnp.issubdtype(
                jnp.asarray(x).dtype, jnp.floating) else jnp.asarray(x), params)
        kv_dtype = jnp.dtype(c.kv_cache_dtype) if c.kv_cache_dtype else dtype
        self.kv = BlockedKVCache(self.cfg.num_layers, c.num_kv_blocks,
                                 c.kv_block_size, self.cfg.kv_heads,
                                 self.cfg.head_dim, dtype=kv_dtype,
                                 enable_prefix_index=c.enable_prefix_cache)
        self.state_manager = DSStateManager(self.kv)
        self.reuse = ReuseStats()
        if c.spec_decode_k < 0 or c.spec_ngram < 1:
            raise ValueError(f"spec_decode_k={c.spec_decode_k} must be >= 0 "
                             f"and spec_ngram={c.spec_ngram} >= 1")
        if c.spec_decode_k > 0 and not c.greedy:
            raise ValueError(
                "spec_decode_k > 0 requires greedy=True: the acceptance rule "
                "compares argmax streams, which has no sampled analogue here")
        self.wrapper = RaggedBatchWrapper(token_budget=c.token_budget,
                                          max_seqs=c.max_ragged_sequence_count,
                                          max_chunk=c.max_chunk_size,
                                          max_blocks_per_seq=c.max_blocks_per_seq)
        self._key = jax.random.PRNGKey(c.seed)
        for knob in (c.attn_backend, c.decode_attn_backend,
                     getattr(self.cfg, "decode_attn_impl", "auto")):
            if knob not in ("auto", "pallas", "einsum"):
                raise ValueError(f"attn backend must be auto|pallas|einsum, "
                                 f"got {knob!r}")
        if c.attn_backend == "pallas" and self._special_attn:
            raise ValueError(
                "attn_backend='pallas' computes plain scaled causal "
                "attention; ALiBi / layer_windows families "
                "run on the einsum path — use attn_backend='auto'")
        # packed/prefill path: the legacy chunk kernel takes fp pools in the
        # compute dtype (quantized and storage-cast pools dequantize on the
        # einsum gather); the FUSED DECODE kernel below has no such limit
        if c.attn_backend == "auto":
            self.attn_impl = ("pallas" if jax.default_backend() == "tpu"
                              and kv_dtype == dtype
                              and not self._special_attn else "einsum")
        elif c.attn_backend == "pallas" and kv_dtype != dtype:
            _warn_decode_once(
                f"attn_backend='pallas' with kv_cache_dtype={c.kv_cache_dtype}: "
                "the packed-step kernel takes compute-dtype pools, so prompt "
                "chunks run the einsum gather; the fused decode path keeps "
                "the pallas kernel (int8 dequant fused in-kernel)")
            self.attn_impl = "einsum"
        else:
            self.attn_impl = c.attn_backend
        self.decode_attn_impl, self.decode_attn_source = \
            self._resolve_decode_attn(kv_dtype, dtype)
        self._record_decode_plan(kv_dtype)
        self.steps = 0
        self.last_num_scheduled = 0
        log_dist(f"inference v2: budget={c.token_budget} seqs={c.max_ragged_sequence_count} "
                 f"chunk={c.max_chunk_size} blocks={c.num_kv_blocks}x{c.kv_block_size} "
                 f"attn={self.attn_impl} decode_attn={self.decode_attn_impl}"
                 f"({self.decode_attn_source})")

    # ------------------------------------------------------------------
    # decode-attention resolution (model field > serving/engine config >
    # planner > heuristic — the resolve_loss_impl order)
    # ------------------------------------------------------------------
    def _decode_attn_site(self, kv_dtype):
        """The planner-IR site for this engine's fused-decode attention:
        ``shape`` is the gathered pool view one decode step would
        materialize on the einsum path ([S, B*bs, Hk, D], ONE pool) in the
        STORAGE dtype — the cost model's decode-shape regime prices both
        impls from it."""
        from ...comm.planner.ir import make_site

        c = self.config
        return make_site(op="decode_attn",
                         shape=(c.max_ragged_sequence_count,
                                c.max_blocks_per_seq * c.kv_block_size,
                                self.cfg.kv_heads, self.cfg.head_dim),
                         dtype=kv_dtype, axes=(), consumer="decode")

    def _decode_structural_bail(self, kv_dtype, dtype) -> Optional[str]:
        """Why the fused decode kernel cannot serve this model/pool, or
        None. The kernel computes plain scaled causal attention over
        compute-dtype or int8 (values, scales) pools."""
        if self.cfg.position == "alibi":
            return "the ALiBi per-head bias rides the logits"
        if self.cfg.layer_windows is not None:
            return "per-layer attention windows mask the logits"
        if kv_dtype != dtype and kv_dtype != jnp.dtype(jnp.int8):
            return (f"kv_cache_dtype={self.config.kv_cache_dtype} "
                    "storage-cast pools dequantize on the gather path")
        if jax.default_backend() == "tpu" and self.cfg.head_dim % 128:
            # the kernel compiles and agrees with the einsum path at
            # head_dim 64 (tests/unit/test_tpu_hardware.py), but XLA:TPU
            # keeps a pool whose rows are narrower than the 128 lanes in a
            # slot-minor layout, and the kernel's row-major operand then
            # makes it copy BOTH whole pools (padded to the lane width) on
            # every call: 4x the pool's bytes of temporaries per step
            return (f"head_dim {self.cfg.head_dim} is not a 128-lane "
                    "multiple: the resident-pool kernel would make XLA "
                    "relayout the whole pool on every call")
        return None

    def _resolve_decode_attn(self, kv_dtype, dtype):
        """-> (impl, source). An explicit model field wins, then the
        engine/serving config (decode_attn_backend, then the shared
        attn_backend), then a planner decision (``decode_attn`` first-class
        op), then the accelerator heuristic; a structural bail demotes a
        pallas pick to einsum with a one-time warning instead of the old
        silent hard-pin."""
        c = self.config
        want, source = "auto", "heuristic"
        if getattr(self.cfg, "decode_attn_impl", "auto") != "auto":
            want, source = self.cfg.decode_attn_impl, "model"
        elif c.decode_attn_backend != "auto":
            want, source = c.decode_attn_backend, "config"
        elif c.attn_backend != "auto":
            want, source = c.attn_backend, "config"
        if want == "auto":
            from ...comm.planner import get_planner, planner_active

            if planner_active():
                try:
                    d = get_planner().resolve(self._decode_attn_site(kv_dtype))
                except Exception as e:  # noqa: BLE001 - a planner fault must
                    # not block engine bring-up, but it is said, not hidden
                    _warn_decode_once(
                        f"decode_attn planner resolve failed ({e!r}) — "
                        "using the platform heuristic")
                else:
                    if d.impl in ("pallas", "einsum"):
                        want, source = d.impl, "planner"
        if want == "auto":
            want = "pallas" if jax.default_backend() == "tpu" else "einsum"
            source = "heuristic"
        if want == "pallas":
            reason = self._decode_structural_bail(kv_dtype, dtype)
            if reason:
                _warn_decode_once(
                    f"decode_attn='pallas' ({source}) but {reason} — fused "
                    "decode falls back to the gathered-page einsum "
                    "reference (one-time notice)")
                return "einsum", "fallback"
        return want, source

    def _record_decode_plan(self, kv_dtype) -> None:
        """Plan-table row for the resolved decode path: planner-sourced
        decisions were already recorded by ``resolve()``; every other
        source records here, so ``comm.log_summary()``'s plan table (and
        the static auditor's reconciliation) always names which decode
        attention implementation serves this engine."""
        if self.decode_attn_source == "planner":
            return
        from ...comm import get_comms_logger

        site = self._decode_attn_site(kv_dtype)
        get_comms_logger().record_plan(site.signature(), {
            "consumer": "decode", "op": "decode_attn",
            "shape": "x".join(str(d) for d in site.shape),
            "axes": "", "impl": self.decode_attn_impl, "block": None,
            "source": self.decode_attn_source, "est_us": None,
            "mode": "engine"})

    # ------------------------------------------------------------------
    # admission (reference put/query/can_schedule, engine_v2.py:107,158,184)
    # ------------------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        """Unallocated KV pages (reference ``engine_v2.free_blocks``)."""
        return self.kv.free_blocks

    @property
    def uncommitted_free_blocks(self) -> int:
        """Free pages not yet promised to admitted sequences — what
        admission can actually spend (the serving scheduler's feasibility
        input)."""
        return self.kv.free_blocks - self._outstanding_blocks()

    def get_remaining_block_capacity(self, uid: int) -> int:
        """Tokens a sequence can still append before needing a new page
        (reference ``engine_v2.get_remaining_block_capacity``)."""
        seq = self.state_manager.get(uid)
        if seq is None:
            return 0
        bs = self.config.kv_block_size
        return (-seq.seen_tokens) % bs

    def put(self, uids: Sequence[int], tokens_list: Sequence[np.ndarray],
            max_new_tokens: int = 256, eos_token_id: Optional[int] = None):
        """Admit new sequences (prompts are scheduled incrementally)."""
        for uid, toks in zip(uids, tokens_list):
            toks = np.asarray(toks, np.int32).reshape(-1)
            ok, why = self.can_schedule(len(toks), max_new_tokens)
            if not ok:
                raise RuntimeError(f"cannot schedule uid={uid}: {why}")
            seq = self.state_manager.create(uid, toks,
                                            max_new_tokens=max_new_tokens,
                                            eos_token_id=eos_token_id)
            self._map_cached_prefix(seq)

    def _map_cached_prefix(self, seq) -> None:
        """Prefix-cache admission: match the longest chain of registered
        full blocks over the prompt, map those pages into the sequence's
        block table SHARED (refcounted), and advance ``seen_tokens`` so only
        the uncached tail is prefilled. When the whole prompt is covered the
        final prompt token must still run through the forward to produce
        next-token logits, and its KV write would land in the last matched
        (shared) page — that page is copy-on-write-forked first and the
        cursor rewound one token, so the write hits the private copy.

        Runs AFTER ``can_schedule`` accepted the worst case, and only ever
        reduces this sequence's outstanding commitment (mapped pages need no
        fresh allocation), so the PR 7 no-deadlock invariant is untouched.
        """
        idx = self.kv.index
        if idx is None:
            return
        bs = self.config.kv_block_size
        self.reuse.prefix_lookups += 1
        hashes = chain_hashes(seq.prompt_tokens, bs)
        pages = idx.lookup(hashes)
        if not pages:
            return
        m = len(pages)
        plen = len(seq.prompt_tokens)
        self.kv.share(pages)
        seq.blocks = list(pages)
        seq.hash_chain = hashes[:m]
        seq.seen_tokens = m * bs
        shared = m
        if seq.seen_tokens >= plen:
            seq.seen_tokens = plen - 1
            src = seq.blocks[-1]
            fork = self.kv.cow_fork(src)
            seq.blocks[-1] = fork
            self.kv.release(src)
            shared -= 1
            self.reuse.cow_forks += 1
        seq.prefix_reused_tokens = seq.seen_tokens
        self.reuse.prefix_hits += 1
        self.reuse.prefix_tokens_reused += seq.seen_tokens
        self.reuse.prefix_blocks_shared += shared

    def _register_full_blocks(self, seq) -> None:
        """Publish this sequence's newly-FILLED full blocks into the prefix
        index (first writer wins; pages another sequence already advertises
        are skipped by ``register``). Generated tokens count too — a resumed
        request re-admitted with prompt+generated re-matches its own decode
        progress and pays only the tail (PR 15 resumable-serving bugfix).
        Only tokens whose KV is committed are hashable: ``seen_tokens``
        bounds written rows, prompt+generated bounds known content (in
        steady decode ``seen`` trails ``committed`` by the one sampled-but-
        unwritten token)."""
        idx = self.kv.index
        if idx is None:
            return
        bs = self.config.kv_block_size
        committed = len(seq.prompt_tokens) + len(seq.generated)
        n_full = min(min(seq.seen_tokens, committed) // bs, len(seq.blocks))
        chain = seq.hash_chain
        if n_full <= len(chain):
            return
        tokens = np.concatenate(
            [seq.prompt_tokens, np.asarray(seq.generated, np.int32)]) \
            if seq.generated else seq.prompt_tokens
        while len(chain) < n_full:
            i = len(chain)
            digest = hash_block(chain[-1] if chain else ROOT_HASH,
                                tokens[i * bs:(i + 1) * bs])
            chain.append(digest)
            idx.register(digest, seq.blocks[i])

    def _outstanding_blocks(self) -> int:
        """Worst-case blocks already promised to admitted sequences but not
        yet allocated — admission must not over-commit the pool."""
        bs = self.config.kv_block_size
        total = 0
        for seq in self.state_manager.all():
            if seq.done:
                continue
            worst = -(-(len(seq.prompt_tokens) + seq.max_new_tokens) // bs)
            total += max(0, worst - len(seq.blocks))
        return total

    def can_schedule(self, prompt_len: int, max_new_tokens: int) -> Tuple[bool, str]:
        total_len = prompt_len + max_new_tokens
        if total_len > self.cfg.max_seq_len:
            return False, (f"prompt {prompt_len} + max_new {max_new_tokens} exceeds "
                           f"the model's max_seq_len {self.cfg.max_seq_len}")
        blocks_needed = -(-total_len // self.config.kv_block_size)
        if blocks_needed > self.config.max_blocks_per_seq:
            return False, (f"sequence needs {blocks_needed} blocks > "
                           f"max_blocks_per_seq {self.config.max_blocks_per_seq}")
        available = self.uncommitted_free_blocks
        if blocks_needed > available:
            return False, (f"KV pool has {available} uncommitted free blocks "
                           f"(of {self.kv.free_blocks} free), need {blocks_needed}")
        return True, ""

    def query(self, uid: int):
        """(done, generated tokens so far) for a tracked uid."""
        seq = self.state_manager.get(uid)
        if seq is None:
            raise KeyError(f"unknown uid {uid}")
        return seq.done, np.array(seq.generated, np.int32)

    def flush(self, uid: int):
        """Release a sequence's KV blocks and tracking state."""
        self.state_manager.release(uid)

    def has_work(self) -> bool:
        return any((s.in_prefill or (not s.done)) for s in self.state_manager.all())

    def _slice_block_table(self, bt: np.ndarray, pos0: np.ndarray,
                           n: int) -> np.ndarray:
        """Slice the table to the pages this decode window can touch.

        The gather attention reads EVERY table column, so a short context in
        a long table (max_blocks_per_seq sized for max_seq_len) would read
        mostly trash pages. The page count is static per dispatch; rounding
        it up to a power of two caps the distinct compiled programs at
        log2(max_blocks_per_seq) as generation grows across windows.
        """
        bs = self.config.kv_block_size
        b_need = max(1, -(-(int(pos0.max()) + n) // bs))
        b_need = 1 << (b_need - 1).bit_length()
        return bt[:, :min(bt.shape[1], b_need)]

    # ------------------------------------------------------------------
    # one engine step: schedule -> pack -> forward -> sample
    # ------------------------------------------------------------------
    def schedule(self) -> List:
        """Dynamic SplitFuse: decode tokens first (latency), then fill the
        remaining budget with prompt chunks."""
        c = self.config
        budget = c.token_budget
        slots = c.max_ragged_sequence_count
        scheduled = []
        decodes = [s for s in self.state_manager.all()
                   if not s.done and not s.in_prefill and s.generated]
        prefills = [s for s in self.state_manager.all() if s.in_prefill]
        for seq in decodes:
            if budget < 1 or slots < 1:
                break
            toks = seq.next_tokens(1)
            if len(toks):
                self.kv.reserve(seq, len(toks))
                scheduled.append((seq, toks))
                budget -= len(toks)
                slots -= 1
        for seq in prefills:
            if budget < 1 or slots < 1:
                break
            n = min(budget, c.max_chunk_size)
            toks = seq.next_tokens(n)
            if len(toks):
                self.kv.reserve(seq, len(toks))
                scheduled.append((seq, toks))
                budget -= len(toks)
                slots -= 1
        return scheduled

    def step(self) -> Dict[int, int]:
        """Run one packed forward; returns {uid: sampled token} for sequences
        that produced a token this step (a step that only advanced prompt
        chunks returns {} — check ``last_num_scheduled`` for progress)."""
        scheduled = self.schedule()
        self.last_num_scheduled = len(scheduled)
        if not scheduled:
            return {}
        batch = self.wrapper.pack(scheduled, self.config.kv_block_size)
        self._key, step_key = jax.random.split(self._key)
        kv_k, kv_v = self.kv.pool_args()
        sampled, new_k, new_v = ragged_step(
            self.params, self.cfg, kv_k, kv_v,
            jnp.asarray(batch.tokens), jnp.asarray(batch.positions),
            jnp.asarray(batch.gather_idx), jnp.asarray(batch.block_table),
            jnp.asarray(batch.kv_len), jnp.asarray(batch.logits_idx),
            jnp.asarray(batch.start_pos), jnp.asarray(batch.chunk_len),
            step_key, jnp.float32(self.config.temperature),
            attn_impl=self.attn_impl, greedy=self.config.greedy)
        self.kv.update(new_k, new_v)
        sampled = np.asarray(sampled)    # [S] int32 — the only D2H transfer
        out: Dict[int, int] = {}
        for s, (seq, toks) in enumerate(scheduled):
            seq.seen_tokens += len(toks)
        for s in batch.sample_slots:
            seq, _ = scheduled[s]
            tok = int(sampled[s])
            seq.generated.append(tok)
            out[seq.uid] = tok
            if ((seq.eos_token_id is not None and tok == seq.eos_token_id)
                    or len(seq.generated) >= seq.max_new_tokens):
                seq.done = True
        for seq, _ in scheduled:
            self._register_full_blocks(seq)
        self.steps += 1
        return out

    def decode_batch(self, n_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Fused multi-token decode: ``n`` forward+sample iterations for every
        active sequence in ONE compiled program (``model.decode_loop``).

        Requires all active sequences to be past prefill (use ``step()`` for
        mixed prefill/decode batches). Returns {uid: accepted tokens}.
        """
        c = self.config
        seqs = [s for s in self.state_manager.all() if not s.done]
        if not seqs:
            return {}
        if any(s.in_prefill or not s.generated for s in seqs):
            raise RuntimeError("decode_batch requires every active sequence "
                               "past prefill with a first sampled token")
        if len(seqs) > c.max_ragged_sequence_count:
            raise RuntimeError(f"{len(seqs)} active sequences > "
                               f"max_ragged_sequence_count {c.max_ragged_sequence_count}")
        n = min(n_steps or c.decode_chunk,
                min(s.max_new_tokens - len(s.generated) for s in seqs))
        if n < 1:
            return {}
        S, B = c.max_ragged_sequence_count, c.max_blocks_per_seq
        tokens0 = np.zeros((S,), np.int32)
        pos0 = np.zeros((S,), np.int32)
        bt = np.zeros((S, B), np.int32)
        active = np.zeros((S,), bool)
        for slot, seq in enumerate(seqs):
            self.kv.reserve(seq, n)
            tokens0[slot] = seq.generated[-1]
            pos0[slot] = seq.seen_tokens
            bt[slot, :len(seq.blocks)] = seq.blocks
            active[slot] = True
        bt = self._slice_block_table(bt, pos0, n)
        self._key, step_key = jax.random.split(self._key)
        kv_k, kv_v = self.kv.pool_args()
        toks, new_k, new_v = decode_loop(
            self.params, self.cfg, kv_k, kv_v,
            jnp.asarray(tokens0), jnp.asarray(pos0), jnp.asarray(bt),
            jnp.asarray(active), step_key, jnp.float32(c.temperature),
            n_steps=n, attn_impl=self.decode_attn_impl, greedy=c.greedy)
        self.kv.update(new_k, new_v)
        toks = np.asarray(toks)                     # [S, n]
        out: Dict[int, List[int]] = {}
        for slot, seq in enumerate(seqs):
            accepted: List[int] = []
            for t in toks[slot, :n]:
                accepted.append(int(t))
                if ((seq.eos_token_id is not None and int(t) == seq.eos_token_id)
                        or len(seq.generated) + len(accepted) >= seq.max_new_tokens):
                    seq.done = True
                    break
            seq.generated.extend(accepted)
            seq.seen_tokens += n                    # n tokens entered the KV cache
            self._register_full_blocks(seq)
            out[seq.uid] = accepted
        self.steps += 1
        return out

    def _ngram_propose(self, seq, k: int) -> List[int]:
        """Draft up to ``k`` tokens by n-gram lookup: find the most recent
        PRIOR occurrence of the sequence's final ``spec_ngram`` tokens in
        prompt+generated and propose the tokens that followed it. Pure host
        work over int32 context — no draft model, no extra forward."""
        n = self.config.spec_ngram
        ctx = (np.concatenate([seq.prompt_tokens,
                               np.asarray(seq.generated, np.int32)])
               if seq.generated else seq.prompt_tokens)
        L = len(ctx)
        if k < 1 or L <= n:
            return []
        key = ctx[L - n:]
        for start in range(L - n - 1, -1, -1):
            if np.array_equal(ctx[start:start + n], key):
                return [int(t) for t in ctx[start + n:start + n + k]]
        return []

    def spec_decode_batch(self, k: Optional[int] = None) -> Dict[int, List[int]]:
        """N-gram speculative decode: per live sequence, pack the chunk
        ``[last sampled] + drafts`` and verify EVERY position in one packed
        dispatch (``model.verify_step`` returns the greedy argmax after each
        input token). The accepted run of drafts plus the model's own next
        token at the first mismatch are committed; ``seen_tokens`` rewinds
        past the rejected suffix (their KV rows are overwritten when those
        positions are legitimately reached — reads are masked by ``kv_len``
        so stale rows are never visible). Greedy-only: every committed token
        IS an argmax the sequential path would have produced, so the output
        stream is bitwise identical to ``step()``/``decode_batch``.

        Preconditions mirror ``decode_batch`` (all live sequences past
        prefill with a first sampled token). A sequence with no n-gram match
        rides along as a plain 1-token chunk — same dispatch, no divergent
        code path. Returns {uid: committed tokens}."""
        c = self.config
        if not c.greedy:
            raise RuntimeError("spec_decode_batch requires greedy=True (the "
                               "acceptance rule compares argmax streams)")
        k = c.spec_decode_k if k is None else int(k)
        seqs = [s for s in self.state_manager.all() if not s.done]
        if not seqs:
            return {}
        if any(s.in_prefill or not s.generated for s in seqs):
            raise RuntimeError("spec_decode_batch requires every active "
                               "sequence past prefill with a first sampled "
                               "token")
        if len(seqs) > c.max_ragged_sequence_count:
            raise RuntimeError(f"{len(seqs)} active sequences > "
                               f"max_ragged_sequence_count "
                               f"{c.max_ragged_sequence_count}")
        bs = c.kv_block_size
        share = max(1, c.token_budget // len(seqs))
        scheduled: List[Tuple] = []
        drafted: List[List[int]] = []
        for seq in seqs:
            # chunk = 1 + k_i must fit the prompt-chunk cap and the budget
            # share; committing up to k_i + 1 tokens must not overrun
            # max_new_tokens; KV rows for all chunk inputs must fit the
            # block table
            cap = min(k, c.max_chunk_size - 1, share - 1,
                      seq.max_new_tokens - len(seq.generated) - 1,
                      c.max_blocks_per_seq * bs - seq.seen_tokens - 1)
            drafts = self._ngram_propose(seq, cap) if cap > 0 else []
            toks = np.asarray([seq.generated[-1]] + drafts, np.int32)
            self.kv.reserve(seq, len(toks))
            scheduled.append((seq, toks))
            drafted.append(drafts)
        batch = self.wrapper.pack(scheduled, bs)
        kv_k, kv_v = self.kv.pool_args()
        nexts, new_k, new_v = verify_step(
            self.params, self.cfg, kv_k, kv_v,
            jnp.asarray(batch.tokens), jnp.asarray(batch.positions),
            jnp.asarray(batch.gather_idx), jnp.asarray(batch.block_table),
            jnp.asarray(batch.kv_len), jnp.asarray(batch.start_pos),
            jnp.asarray(batch.chunk_len), attn_impl=self.attn_impl)
        self.kv.update(new_k, new_v)
        nexts = np.asarray(nexts)       # [T] int32 — the only D2H transfer
        out: Dict[int, List[int]] = {}
        cursor = 0
        for (seq, toks), drafts in zip(scheduled, drafted):
            preds = nexts[cursor:cursor + len(toks)]
            cursor += len(toks)
            j = 0
            while j < len(drafts) and int(preds[j]) == drafts[j]:
                j += 1
            committed = drafts[:j] + [int(preds[j])]
            self.reuse.spec_drafted += len(drafts)
            self.reuse.spec_accepted += j
            accepted: List[int] = []
            for t in committed:
                accepted.append(int(t))
                if ((seq.eos_token_id is not None
                        and int(t) == seq.eos_token_id)
                        or len(seq.generated) + len(accepted)
                        >= seq.max_new_tokens):
                    seq.done = True
                    break
            seq.generated.extend(accepted)
            # chunk inputs [last] + drafts[:j] are committed content whose
            # KV is now written; rewind past the rejected draft suffix
            seq.seen_tokens += 1 + j
            self._register_full_blocks(seq)
            out[seq.uid] = accepted
        self.reuse.spec_steps += 1
        self.steps += 1
        return out

    def decode_stream(self, total_steps: int) -> Dict[int, List[int]]:
        """Fused decode of ``total_steps`` tokens in ONE dispatch + ONE host
        sync (``model.decode_loop`` scans the whole run on device).

        Generates ``min(total_steps, min remaining)`` tokens, rounded UP to a
        ``decode_chunk`` multiple when KV capacity allows — ``n_steps`` is a
        static jit argument, so rounding keeps repeated calls with staggered
        remaining-counts on ONE compiled program instead of recompiling the
        whole scanned model per distinct count. Tokens past a sequence's EOS
        or ``max_new_tokens`` are discarded on host.
        """
        c = self.config
        if total_steps > c.max_fused_window:
            # Bound the fused window (see max_fused_window). The whole run's
            # step count is capped ONCE by the min remaining budget across
            # the sequences active NOW, so chunking is observationally
            # identical to a single dispatch (a per-chunk re-min would keep
            # generating for budget-rich sequences after a budget-poor one
            # finished, which one big dispatch never does).
            live = [s for s in self.state_manager.all() if not s.done]
            if not live:
                return {}
            total = min(total_steps,
                        min(s.max_new_tokens - len(s.generated) for s in live))
            out: Dict[int, List[int]] = {}
            produced = 0
            while produced < total:
                n = min(c.max_fused_window, total - produced)
                got = self.decode_stream(n)
                if not got:
                    break
                for uid, toks in got.items():
                    out.setdefault(uid, []).extend(toks)
                # the inner call may clamp below the requested n (block-table
                # capacity / free-block fallback): advance by what actually
                # ran, not what was asked (ADVICE r3 — overcounting returned
                # fewer than min(total_steps, budget) without surfacing it)
                step_n = max(len(toks) for toks in got.values())
                if step_n == 0:
                    break  # capacity exhausted (e.g. full block tables):
                           # no progress is possible, don't spin
                produced += step_n
            return out
        seqs = [s for s in self.state_manager.all() if not s.done]
        if not seqs:
            return {}
        if any(s.in_prefill or not s.generated for s in seqs):
            raise RuntimeError("decode_stream requires every active sequence "
                               "past prefill with a first sampled token")
        total = min(total_steps,
                    min(s.max_new_tokens - len(s.generated) for s in seqs))
        if total < 1:
            return {}
        S, B = c.max_ragged_sequence_count, c.max_blocks_per_seq
        bs = c.kv_block_size
        # bucket n_steps (see docstring); cap by per-seq block-table capacity
        # and by the free-block pool, falling back to the exact count
        bucket = -(-total // c.decode_chunk) * c.decode_chunk
        cap = min(B * bs - s.seen_tokens for s in seqs)
        n = min(bucket, cap)
        need = sum(s.blocks_needed(n, bs) for s in seqs)
        if need > self.kv.free_blocks:
            n = total
        tokens0 = np.zeros((S,), np.int32)
        pos0 = np.zeros((S,), np.int32)
        bt = np.zeros((S, B), np.int32)
        active = np.zeros((S,), bool)
        for slot, seq in enumerate(seqs):
            self.kv.reserve(seq, n)
            tokens0[slot] = seq.generated[-1]
            pos0[slot] = seq.seen_tokens
            bt[slot, :len(seq.blocks)] = seq.blocks
            active[slot] = True
        bt = self._slice_block_table(bt, pos0, n)
        self._key, step_key = jax.random.split(self._key)
        kv_k, kv_v = self.kv.pool_args()
        toks, new_k, new_v = decode_loop(
            self.params, self.cfg, kv_k, kv_v,
            jnp.asarray(tokens0), jnp.asarray(pos0), jnp.asarray(bt),
            jnp.asarray(active), step_key, jnp.float32(c.temperature),
            n_steps=n, attn_impl=self.decode_attn_impl, greedy=c.greedy)
        self.kv.update(new_k, new_v)
        self.steps += 1
        all_toks = np.asarray(toks)                 # [S, n]
        out: Dict[int, List[int]] = {}
        for slot, seq in enumerate(seqs):
            accepted: List[int] = []
            for t in all_toks[slot, :n]:
                accepted.append(int(t))
                if ((seq.eos_token_id is not None and int(t) == seq.eos_token_id)
                        or len(seq.generated) + len(accepted) >= seq.max_new_tokens):
                    seq.done = True
                    break
            seq.generated.extend(accepted)
            seq.seen_tokens += n        # every scanned token entered the KV
            self._register_full_blocks(seq)
            out[seq.uid] = accepted
        return out

    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence[np.ndarray], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None) -> List[np.ndarray]:
        """Convenience batch API over the continuous engine: SplitFuse steps
        through prefill, then fused decode chunks."""
        uids = list(range(len(prompts)))
        self.put(uids, prompts, max_new_tokens=max_new_tokens,
                 eos_token_id=eos_token_id)
        while any(s.in_prefill for s in self.state_manager.all() if not s.done):
            self.step()
            if self.last_num_scheduled == 0:
                break
        while any(not self.query(u)[0] for u in uids):
            if eos_token_id is None:
                # no early exit possible: chain all remaining chunks with one
                # host sync (decode_stream never overshoots in this case)
                if not self.decode_stream(max_new_tokens):
                    break
            elif not self.decode_batch():
                break
        outs = [self.query(u)[1] for u in uids]
        for u in uids:
            self.flush(u)
        return outs
