"""The training engine.

TPU-native re-design of ``DeepSpeedEngine`` (reference ``runtime/engine.py:183``).
The reference wraps a torch ``nn.Module`` and orchestrates mixed precision,
gradient accumulation, ZeRO collectives, and the optimizer step imperatively
(hooks + streams). Here the whole training step — microbatch scan, grad
accumulation, loss scaling, clipping, optimizer update, overflow skip — is one
pure function compiled by XLA over the device mesh; ZeRO stages are sharding
rules (``runtime/zero/sharding.py``) on the state pytree, and XLA schedules the
allgather/reduce-scatter traffic the reference issued by hand.

API surface preserved from the reference:
  ``initialize(...) -> engine`` (``deepspeed/__init__.py:69``);
  ``engine.train_batch`` / ``engine.eval_batch``;
  compat ``forward``/``backward``/``step`` (``engine.py:1848,2007,2204``);
  ``save_checkpoint``/``load_checkpoint`` (``engine.py:3140,2794``).
"""

import contextlib
import inspect
import json
import os
import time
from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..ops.optimizers import build_optimizer
from ..telemetry.spans import span
from ..parallel.topology import Topology, TopologySpec, get_topology, set_topology
from ..utils.logging import log_dist, logger
from .config import DeepSpeedTPUConfig, load_config
from .config_utils import ConfigError
from .loss_scaler import (LossScaleState, has_overflow, make_loss_scale_state,
                          update_loss_scale)
from .lr_schedules import build_lr_schedule
from .zero.sharding import ZeroShardingRules

try:
    from flax import struct
except ImportError:  # pragma: no cover
    struct = None

# the control plane's remat escalation ladder (engine.raise_remat): no
# remat -> keep only matmul outputs -> keep nothing (max memory headroom,
# max recompute). Each entry names a jax.checkpoint_policies member
# (None = unwrapped); a custom configured policy escalates straight to
# the last rung.
REMAT_LADDER = (None, "dots_saveable", "nothing_saveable")


def artifact_rank() -> int:
    """The rank stamped on per-rank post-mortem artifacts (flightdumps,
    hangdumps, heartbeat beacons, doctor reports). ``jax.process_index()``
    when the control plane is genuinely multi-process; otherwise the
    launcher's ``DSTPU_PROCESS_ID`` env — fake-fleet drills run N
    *independent* single-process jax instances against one dump dir, and
    they must not all claim rank 0 — defaulting to 0."""
    if jax.process_count() > 1:
        return jax.process_index()
    try:
        return int(os.environ.get("DSTPU_PROCESS_ID", "0") or 0)
    except ValueError:
        return 0


@struct.dataclass
class TrainState:
    """Engine state pytree. ``params`` are fp32 master weights (reference
    FP16/BF16 optimizer master copies, ``runtime/fp16/fused_optimizer.py:33``,
    ``bf16_optimizer.py:34``) unless master weights are disabled.

    ``comm_feedback`` is the cross-step error-feedback residual of a
    DCN-compressed gradient program (``comm/compressed.py``
    ``run_collective_program`` with an ``int8_ef`` hop): engine-OWNED state,
    threaded through the jitted step like the optimizer state, so one
    residual accumulates across steps (instead of a fresh zero per trace)
    and it rides resilience snapshots — a rollback restores the snapshot's
    residual rather than replaying the abandoned trajectory's. Empty
    (``()`` — zero pytree leaves) whenever feedback is off, which keeps
    every default-off path structurally and bitwise identical."""
    step: jnp.ndarray
    params: Any
    opt_state: Any
    loss_scale: LossScaleState
    comm_feedback: Any = ()


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def global_grad_norm(grads) -> jnp.ndarray:
    """L2 norm across the whole grad pytree (reference ``clip_grad_norm_``,
    ``runtime/utils.py:315`` — the cross-rank reduction is implicit in SPMD)."""
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _path_key(entry) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _struct_congruent_specs(state_shapes, params, param_spec_tree):
    """Build a PartitionSpec tree congruent to an optimizer-state pytree.

    Optimizer states are built of params-congruent subtrees (momenta, master
    copies) plus scalars (step counters). A state leaf whose key-path *suffix*
    and shape match a param gets that param's spec; everything else is
    replicated. Works for arbitrarily nested optax chain states.
    """
    param_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_leaves = jax.tree.leaves(param_spec_tree, is_leaf=lambda x: isinstance(x, P))
    lookup = {}
    for (path, leaf), spec in zip(param_leaves, spec_leaves):
        lookup[(tuple(_path_key(e) for e in path), leaf.shape)] = spec

    max_plen = max((len(k[0]) for k in lookup), default=0)

    def spec_for(path, leaf):
        if not hasattr(leaf, "shape") or leaf.shape == ():
            return P()  # spec-ok: scalar leaves replicate
        keys = tuple(_path_key(e) for e in path)
        for take in range(min(len(keys), max_plen), 0, -1):
            spec = lookup.get((keys[-take:], leaf.shape))
            if spec is not None:
                return spec
        return P()  # spec-ok: lookup fallback: replicate unknown leaves

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    return jax.tree_util.tree_unflatten(treedef, [spec_for(p, l) for p, l in flat])


def _abstract_params(params):
    """Shape tree for possibly-lazy params (the zero.Init closure form)."""
    return (jax.eval_shape(params)
            if callable(params) and not hasattr(params, "shape") else params)


def _frozen_label_tree(params, patterns: Sequence[str]):
    """'freeze'/'train' label per leaf: a leaf freezes when any pattern hits
    its '/'-joined path at a name-component boundary (same matching contract
    as AutoTP's name vocabulary). A pattern matching NOTHING is an error —
    a typo'd pattern silently training everything (and materializing full
    Adam state) is exactly what the user asked to avoid."""
    import re

    def hit(pattern: str, path: str) -> bool:
        return re.search(rf"(^|[/_.\-]){re.escape(pattern)}([/_.\-]|$)",
                         path) is not None

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = ["/".join(str(getattr(e, "key", getattr(e, "name", e))) for e in kp)
             for kp, _ in flat]
    unmatched = [p for p in patterns if not any(hit(p, path) for path in paths)]
    if unmatched:
        raise ValueError(f"frozen_params patterns {unmatched} match no "
                         f"parameter path; available paths include "
                         f"{paths[:8]}...")
    labels = ["freeze" if any(hit(p, path) for p in patterns) else "train"
              for path in paths]
    return jax.tree_util.tree_unflatten(treedef, labels)


class DeepSpeedTPUEngine:
    def __init__(self,
                 loss_fn: Callable,
                 params: Any,
                 config: DeepSpeedTPUConfig,
                 topology: Optional[Topology] = None,
                 param_specs: Any = None,
                 batch_spec: Any = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 lr_scheduler: Optional[Callable] = None,
                 donate_state: bool = True,
                 autotp_example_batch: Any = None,
                 frozen_params: Optional[Sequence[str]] = None):
        self.config = config
        self.topo = topology or get_topology()
        set_topology(self.topo)
        config.finalize(world_dp_size=self.topo.dp_size)
        # compressed collectives: flip the fleet-wide default the wiring
        # reads (comm/compressed.py — the set_overlap_enabled pattern)
        cc = config.compressed_collectives
        from ..comm.compressed import configure_compression
        configure_compression(cc.mode, block=cc.block,
                              hierarchical=cc.hierarchical,
                              sites=cc.site_map())
        # collective planner (comm/planner): snapshot the explicitly-set
        # raw knobs (they keep winning at their sites) and stand up the
        # fleet planner in the configured mode — off is inert
        from ..comm.planner import configure_from_config
        configure_from_config(config, topology=self.topo)
        # training fast path (ops/fastpath.py): flip the fleet defaults the
        # attention/loss/embedding wirings read when the model config says
        # 'auto' — same pattern as configure_compression above
        tf = config.training_fastpath
        from ..ops.fastpath import configure_fastpath
        configure_fastpath(attn_impl=tf.attn_impl, loss_impl=tf.loss_impl,
                           embedding_overlap=tf.embedding_overlap)
        # engine-level rematerialization: with activation_checkpointing
        # .engine_wrap, ``policy`` names a jax.checkpoint_policies entry
        # applied around the whole loss fn (None never wraps — bit-
        # identical). engine_wrap is opt-in because the per-layer compat
        # API (checkpointing.checkpoint) reads the SAME policy field —
        # wrapping the engine on top would double-rematerialize those
        # models. Read at trace time: the control plane's raise_remat()
        # actuator climbs REMAT_LADDER and invalidates the compiled steps.
        ac = config.activation_checkpointing
        self._remat_policy = ac.policy if ac.engine_wrap else None
        if (optimizer is not None and callable(optimizer)
                and not hasattr(optimizer, "update")):
            # reference DeepSpeedOptimizerCallable (deepspeed/__init__.py:112):
            # a client factory taking model parameters; here it must return
            # an optax GradientTransformation. The factory sees the ABSTRACT
            # tree (shapes/dtypes/structure) so the zero.Init closure form
            # stays lazy — masked/multi_transform-style factories only need
            # the structure anyway
            optimizer = optimizer(_abstract_params(params))
            if not hasattr(optimizer, "update"):
                raise TypeError(
                    "optimizer callable must return an optax "
                    f"GradientTransformation, got {type(optimizer).__name__}")
            log_dist("using client callable to create basic optimizer")
        self._client_optimizer = optimizer is not None  # resilience lr_drop warning
        self.loss_fn_raw = loss_fn
        self._loss_takes_rng = _accepts_rng(loss_fn)
        self._loss_takes_ltd = _accepts_kw(loss_fn, "ltd_keep")
        self.gas = config.gradient_accumulation_steps
        self.micro_batch_size = config.train_micro_batch_size_per_gpu
        self.train_batch_size = config.train_batch_size

        zc = config.zero_optimization
        self.rules = ZeroShardingRules(zc.stage, self.topo, mics_shard_size=zc.mics_shard_size)
        from ..sharding.rules import (ForeignModelShardingError, RuleSet,
                                      spec_tree_axis_sizes)
        if isinstance(param_specs, RuleSet):
            # declarative sharding: match the rule set over the (possibly
            # lazy) param tree; axis_sizes validates mesh membership and
            # downgrades indivisible dims instead of failing at compile
            param_specs = param_specs.match(
                _abstract_params(params),
                axis_sizes=spec_tree_axis_sizes(self.topo))
        if (param_specs is None and self.topo.tp_size > 1
                and not getattr(loss_fn, "_sharding_native", False)):
            # a foreign apply_fn + param tree at tp>1 with no specs would
            # silently replicate every parameter over the tp axis — dense
            # compute on every rank, none of the TP fast paths. Refuse.
            raise ForeignModelShardingError(
                "tp_size={} with no param_specs and a non-TransformerLM "
                "model: parameters would silently replicate over the tp "
                "axis. Pass param_specs='auto' (AutoTP inference), a "
                "sharding.RuleSet (e.g. sharding.get_pack(...) or "
                "sharding.derive_rules(...)), an explicit spec tree, or "
                "load the checkpoint through "
                "sharding.autotp_initialize().".format(self.topo.tp_size))
        if isinstance(param_specs, str) and param_specs == "auto":
            # AutoTP (reference module_inject/auto_tp.py:189): infer TP
            # PartitionSpecs from the param tree. With an example batch the
            # jaxpr dataflow analysis classifies col/row from the program;
            # otherwise the reference's name vocabulary decides.
            from ..module_inject import tp_parser
            abstract = _abstract_params(params)
            if autotp_example_batch is not None:
                if self._loss_takes_rng:
                    trace_fn = lambda p, b: loss_fn(p, b, jax.random.PRNGKey(0))  # noqa: E731
                else:
                    trace_fn = loss_fn
                param_specs = tp_parser(
                    abstract, apply_fn=trace_fn,
                    example_inputs=(autotp_example_batch,),
                    tp_size=self.topo.tp_size)
            else:
                param_specs = tp_parser(abstract, tp_size=self.topo.tp_size)
        self.param_specs_base = param_specs
        self._offload_optimizer = zc.offload_optimizer.device in ("cpu", "nvme")
        # True host-offload (ZeRO-Offload): device=cpu + an adam-family config
        # optimizer runs the update ON HOST via the native kernel
        # (csrc/adam/cpu_adam.cpp); optimizer state never exists on device.
        # A custom optax optimizer or non-adam type falls back to pinned-host
        # storage with on-device compute (the previous tier).
        self._host_adam = None
        self._host_adam_mode = (
            zc.offload_optimizer.device == "cpu" and optimizer is None
            and config.optimizer.type.lower().replace("_", "") in
            ("adam", "adamw", "fusedadam", "cpuadam", "deepspeedcpuadam"))
        if self._host_adam_mode and config.fp16.enabled:
            raise ValueError(
                "fp16 dynamic loss scaling is not supported with "
                "offload_optimizer.device='cpu' (the host Adam step runs "
                "outside the scaled program); use bf16 — the TPU default")
        if self._host_adam_mode and jax.process_count() > 1:
            # host Adam needs fully-addressable grads; on a multi-process
            # mesh fall back to the pinned-host storage tier
            log_dist("offload_optimizer.device=cpu: multi-process mesh — "
                     "falling back to pinned-host optimizer state with "
                     "on-device compute")
            self._host_adam_mode = False

        # --- precision ---------------------------------------------------
        self.compute_dtype = config.compute_dtype
        self.fp16 = config.fp16.enabled
        self.master_weights = (config.bf16.master_weights if config.bf16.enabled else True)

        # --- optimizer ---------------------------------------------------
        sched_params = dict(config.scheduler.params)
        opt_params = dict(config.optimizer.params)
        base_lr = opt_params.get("lr", 1e-3)
        if lr_scheduler is not None:
            self.lr_schedule = lr_scheduler
        else:
            self.lr_schedule = build_lr_schedule(config.scheduler.type, sched_params, base_lr)
        # resilience rollback may drop the LR (sentinel lr_drop_factor):
        # the scale is a trace-time constant read when a step (re)compiles;
        # ResilienceManager invalidates the compiled steps when it changes.
        # Only wrapped when the subsystem is on — off stays byte-for-byte
        # the schedule the optimizer was always built with.
        self._lr_scale = 1.0
        if config.resilience.enabled:
            _base_schedule = self.lr_schedule
            self.lr_schedule = lambda step: _base_schedule(step) * self._lr_scale
        if optimizer is not None:
            self.tx = optimizer
        else:
            # with resilience on, the optimizer must see the WRAPPED schedule
            # even when no scheduler is configured — a constant base_lr float
            # here would make the sentinel's lr_drop_factor a silent no-op on
            # the actual updates while the metrics reported the drop
            use_schedule = config.scheduler.type or config.resilience.enabled
            opt_params["lr"] = self.lr_schedule if use_schedule else base_lr
            self.tx = build_optimizer(config.optimizer.type, opt_params)

        # --- frozen parameters (reference requires_grad=False / the
        # SimpleFrozenModel tier): path patterns select leaves that get NO
        # update and NO optimizer state (multi_transform routes them to
        # set_to_zero, so Adam moments for frozen leaves never exist —
        # the memory-relevant half of freezing under ZeRO) -----------------
        self.frozen_patterns = tuple(frozen_params or ())
        if self.frozen_patterns:
            if self._host_adam_mode:
                log_dist("frozen_params: host-Adam offload tier does not "
                         "mask updates — using pinned-host state with "
                         "on-device compute instead")
                self._host_adam_mode = False
            self._frozen_labels = _frozen_label_tree(_abstract_params(params),
                                                     self.frozen_patterns)
            self.tx = optax.multi_transform(
                {"train": self.tx, "freeze": optax.set_to_zero()},
                self._frozen_labels)

        # --- place state on the mesh ------------------------------------
        self._build_state(params)
        self._build_specs(batch_spec)
        # kept for reconfigure_step(): a control-plane knob change (gas,
        # micro-batch, a re-planned dp-grad transport) re-runs _compile
        self._donate_state = donate_state
        # the training dataloader, when initialize() built one — its batch
        # shape is fixed outside the engine, so halve_micro_batch refuses
        # while one is attached (set regardless of resilience)
        self._train_dataloader = None
        self._compile(donate_state)

        # compat-path buffers (forward/backward/step API)
        self._compat_acc = None
        self._compat_batch = None
        self._compat_pending = None
        self._compat_count = 0
        self._no_sync_depth = 0
        self._micro_step_fn = None
        self._apply_fn = None
        self._eval_fn = None

        self.global_steps = 0
        self._skipped_base = 0
        self._skipped_dev = jnp.zeros([], jnp.int32)
        self._metrics_dev: Optional[Dict[str, Any]] = None
        self._metrics_host: Optional[Dict[str, float]] = {}
        self.monitor = None
        if any(m.enabled for m in (config.monitor.tensorboard, config.monitor.wandb,
                                   config.monitor.csv_monitor, config.monitor.comet)):
            from ..monitor import MonitorMaster

            self.monitor = MonitorMaster(config.monitor)
        self.flops_profiler = None
        self._last_batch = None
        self._step_times = []

        # data-efficiency hooks (reference engine.py:354-358, 1887-1890)
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        de = config.data_efficiency
        if de.enabled:
            cl = de.data_sampling.get("curriculum_learning", {})
            # legacy single-schedule form builds the engine-side scheduler
            # (seqlen truncation in train_batch); the curriculum_metrics
            # form instead drives sample SELECTION through the dataloader's
            # DeepSpeedDataSampler (see initialize/build_curriculum_sampler)
            if cl.get("enabled") and any(
                    k in cl for k in ("curriculum_type", "schedule_type",
                                      "schedule_config")):
                from .data_pipeline import CurriculumScheduler

                self.curriculum_scheduler = CurriculumScheduler(cl)
            rl = de.data_routing.get("random_ltd", {})
            if rl.get("enabled"):
                from .data_pipeline import RandomLTDScheduler

                self.random_ltd_scheduler = RandomLTDScheduler(de.data_routing)
                if not self._loss_takes_ltd:
                    logger.warning(
                        "random_ltd is enabled but the loss fn does not accept an "
                        "'ltd_keep' kwarg — token dropping will NOT be applied. "
                        "Accept ltd_keep (tokens to keep per layer) and wrap layers "
                        "with data_pipeline.random_ltd_apply.")
        # MoQ (reference quantize_training section): fake-quantize weights in
        # the forward at the scheduler's current bit-width; each distinct
        # width is one compiled program (bounded by the bit halvings)
        self.moq = None
        qt = config.quantize_training
        if qt is not None and qt.enabled:
            from .quantize import MoQQuantizer

            self.moq = MoQQuantizer.from_config(qt)
        if config.progressive_layer_drop.enabled:
            logger.warning(
                "progressive_layer_drop is enabled in the config, but layer "
                "drop needs model cooperation (as in the reference): build "
                "the schedule with ProgressiveLayerDrop.from_config and gate "
                "layers with progressive_layer_drop.pld_apply in the loss fn")
        # telemetry spine (deepspeed_tpu/telemetry/): span tracer + flight
        # recorder + metrics registry. Constructed BEFORE resilience so the
        # restore-on-restart path is already on the timeline; attached after
        # so flight dumps ride the watchdog/rollback/drain paths. Off by
        # default: nothing constructed, stepping bit-identical.
        self.telemetry = None
        self.artifact_rank = artifact_rank()
        if config.telemetry.enabled:
            from ..telemetry import TelemetryManager

            self.telemetry = TelemetryManager(
                config.telemetry, rank=self.artifact_rank,
                default_dir=config.resilience.snapshot_dir)
        # chaos engine (runtime/resilience/chaos.py): deterministic fault
        # schedules across transport/serving/control. Installed BEFORE
        # resilience so the manager can adopt the schedule's training
        # FaultPlan. Off by default: the global stays None and every
        # injection site is a single attribute test — bitwise off-identity.
        if config.chaos.enabled:
            from .resilience.chaos import install_chaos_from_config

            install_chaos_from_config(config.chaos)
        else:
            # an engine built WITHOUT a chaos block must not inherit a
            # schedule a previous drill ENGINE installed in this process
            # (the off-identity contract is per-config); schedules
            # installed manually via configure_chaos are left alone
            from .resilience.chaos import clear_config_chaos

            clear_config_chaos()
        # resilience (runtime/resilience/): snapshots + sentinel + preemption.
        # Constructed only when enabled, restore-on-restart runs before the
        # first step so a relaunch continues where the last snapshot left off.
        self.resilience = None
        if config.resilience.enabled:
            from .resilience import ResilienceManager

            self.resilience = ResilienceManager(self, config.resilience)
            if config.resilience.restore_on_start:
                self.resilience.maybe_restore()
        if self.telemetry is not None:
            self.telemetry.attach_engine(self)
        # control plane (deepspeed_tpu/control/): the supervisor policy
        # closing telemetry -> knobs. Constructed AFTER resilience and
        # telemetry so it can tap the health table, the memory gauges, and
        # ride the flight dumps. Off by default: a None attribute the step
        # path checks once — stepping stays bit-identical.
        self.control = None
        if config.control.enabled and config.control.supervisor.enabled:
            from ..control import ControlSupervisor

            self.control = ControlSupervisor.for_engine(self, config.control)
        log_dist(f"engine initialized: {self.topo}, zero_stage={zc.stage}, "
                 f"gas={self.gas}, micro_bs={self.micro_batch_size}, "
                 f"dtype={jnp.dtype(self.compute_dtype).name}")
        from ..utils.memory import see_memory_usage

        see_memory_usage("after engine init", force=config.memory_breakdown)

    # ------------------------------------------------------------------
    def _build_state(self, params):
        rules, topo = self.rules, self.topo
        store_dtype = jnp.float32 if self.master_weights else self.compute_dtype
        if callable(params) and not hasattr(params, "shape"):
            # zero.Init analogue (reference partition_parameters.py:816):
            # ``params`` is a zero-arg init closure. jax.eval_shape derives
            # the tree abstractly (nothing materializes), the ZeRO specs are
            # computed from the abstract shapes, and jitting the closure with
            # out_shardings materializes every leaf DIRECTLY into its shard —
            # no full-size host or device buffer ever exists, so models
            # larger than host RAM can initialize. Per-shard randomness comes
            # from partitionable threefry (XLA generates only local shards).
            init_fn = params

            def cast_init():
                return jax.tree.map(
                    lambda p: p.astype(store_dtype) if jnp.issubdtype(
                        p.dtype, jnp.floating) else p, init_fn())

            abstract = jax.eval_shape(cast_init)
            self.param_spec_tree = rules.param_spec_tree(abstract, self.param_specs_base)
            param_sh = rules.shardings(self.param_spec_tree)
            params = jax.jit(cast_init, out_shardings=param_sh)()
        else:
            # jnp.array (copy=True), NOT asarray: device_put can alias the
            # caller's buffers, and the donated train step would then delete
            # the user's own model_parameters arrays out from under them
            params = jax.tree.map(
                lambda p: jnp.array(p, store_dtype) if jnp.issubdtype(
                    jnp.asarray(p).dtype, jnp.floating) else jnp.array(p), params)
            self.param_spec_tree = rules.param_spec_tree(params, self.param_specs_base)
            param_sh = rules.shardings(self.param_spec_tree)
            params = jax.device_put(params, param_sh)

        if self._host_adam_mode:
            # ZeRO-Offload: fp32 master + moments live on HOST (native SIMD
            # Adam, csrc/adam/cpu_adam.cpp); the device keeps only the
            # compute-dtype working copy. Reference cpu_adam_impl.cpp flow.
            from ..ops.adam import DeepSpeedCPUAdam

            op = dict(self.config.optimizer.params)
            self._host_adam = DeepSpeedCPUAdam(
                jax.device_get(params),  # sync-ok: one-time offload init
                lr=op.get("lr", 1e-3), betas=tuple(op.get("betas", (0.9, 0.999))),
                eps=op.get("eps", 1e-8),
                weight_decay=op.get("weight_decay", 0.0),
                adamw_mode=op.get("adam_w_mode", op.get("adamw_mode", True)),
                bias_correction=op.get("bias_correction", True))
            if self.compute_dtype != jnp.dtype(jnp.float32):
                cast_sh = param_sh

                def to_compute(t):
                    return jax.tree.map(
                        lambda x: x.astype(self.compute_dtype) if jnp.issubdtype(
                            x.dtype, jnp.floating) else x, t)

                params = jax.jit(to_compute, out_shardings=cast_sh,
                                 donate_argnums=(0,))(params)
            opt_state, opt_sh = (), ()
        else:
            opt_shapes = jax.eval_shape(self.tx.init, params)
            # master/optimizer state shards at stage>=1 even when params don't
            opt_param_specs = rules.opt_spec_tree(params, self.param_specs_base)
            opt_spec_tree = _struct_congruent_specs(opt_shapes, params, opt_param_specs)
            opt_sh = jax.tree.map(lambda s: NamedSharding(topo.mesh, s), opt_spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))
            opt_state = jax.jit(self.tx.init, out_shardings=opt_sh)(params)
            if self._offload_optimizer:
                if _host_memory_jit_supported(topo.mesh):
                    # opt_sh updates to pinned-host kinds so every later
                    # device_put (checkpoint load, reload_states) restores
                    # host residency
                    opt_state, opt_sh = _to_host_memory(opt_state, opt_sh)
                else:
                    log_dist("offload_optimizer: this backend cannot compile "
                             "pinned-host operands — optimizer state stays "
                             "device-resident (graceful degradation)")

        # the scalars go onto the mesh like every other leaf: a step leaves
        # them there (out_shardings), and a first call that saw them
        # unplaced would be traced and compiled a second time for the types
        # the second call brings
        step, ls = jax.device_put(
            (jnp.zeros([], jnp.int32),
             make_loss_scale_state(self.config.fp16.initial_scale_power,
                                   self.config.fp16.loss_scale,
                                   self.config.fp16.hysteresis)),
            topo.replicated())
        self.state = TrainState(step=step, params=params,
                                opt_state=opt_state, loss_scale=ls)
        self._opt_shardings = opt_sh
        self._param_shardings = param_sh

    def _build_specs(self, batch_spec):
        topo = self.topo
        dp_axes = topo.dp_axes
        if batch_spec is None:
            if topo.sp_size > 1:
                batch_spec = P(dp_axes, "sp")  # spec-ok: default batch layout when none configured (dp x sp)
            else:
                batch_spec = P(dp_axes)  # spec-ok: default batch layout when none configured (dp)
        self.batch_spec = batch_spec
        self.batch_sharding = NamedSharding(topo.mesh, batch_spec)
        self.grad_spec_tree = self.rules.grad_spec_tree(self.state.params, self.param_specs_base)

    # ------------------------------------------------------------------
    def _loss(self, params, batch, rng, ltd_keep=None, moq_bits=None):
        p = jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        if moq_bits is not None and moq_bits < 16:
            # MoQ fake-quantize at the schedule's current width (static under
            # jit; the step cache keys on it)
            p = self.moq.quantize(p, step=0, training=True, bits=moq_bits)
        kw = {}
        if ltd_keep is not None and self._loss_takes_ltd:
            kw["ltd_keep"] = ltd_keep
        if self._loss_takes_rng:
            call = lambda p_, b_: self.loss_fn_raw(p_, b_, rng, **kw)  # noqa: E731
        else:
            call = lambda p_, b_: self.loss_fn_raw(p_, b_, **kw)  # noqa: E731
        if self._remat_policy is not None:
            # engine-level remat (activation_checkpointing.policy / the
            # control plane's raise_remat): the backward pass recomputes
            # this forward instead of keeping its intermediates — values
            # identical, activation memory traded for recompute. Trace-time
            # read; a policy change invalidates the compiled steps.
            from .activation_checkpointing import checkpoint_wrapper

            call = checkpoint_wrapper(call, self._remat_policy)
        out = call(p, batch)
        if isinstance(out, tuple):
            return out[0].astype(jnp.float32), out[1]
        return out.astype(jnp.float32), None

    def _opt_to_device(self, opt_state):
        """Pinned-host STORAGE tier (the host-Adam decline path: frozen
        params / custom optimizer / multi-process): optimizer state lives in
        host memory between steps; stream it to device memory for the update
        (XLA overlaps the transfer), and the host-kind out_shardings stream
        the new state back. No-op when the optimizer is device-resident."""
        if not (self._offload_optimizer and jax.tree.leaves(opt_state)):
            return opt_state
        return jax.tree.map(
            lambda x, sh: (jax.device_put(x, sh.with_memory_kind("device"))
                           if sh.memory_kind == "pinned_host" else x),
            opt_state, self._opt_shardings)

    def _compile(self, donate_state):
        config, topo, rules = self.config, self.topo, self.rules
        gas, fp16 = self.gas, self.fp16
        clip = config.gradient_clipping
        fp16_dynamic = fp16 and config.fp16.loss_scale == 0
        gd_raw = config.zero_optimization.offload_optimizer.grad_dtype.lower()
        gd_table = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                    "float32": jnp.float32, "fp32": jnp.float32}
        if gd_raw not in gd_table:
            # fp16 is deliberately absent: transport narrowing happens after
            # the finite check, so an fp16 overflow (|g| > 65504) would slip
            # inf past _apply_host_adam's grad_norm gate into the masters;
            # bf16 shares the fp32 exponent range and cannot overflow
            raise ValueError(
                f"offload_optimizer.grad_dtype={gd_raw!r}: use 'float32' or "
                "'bfloat16' (fp16 transport would need its own overflow "
                "gate — bf16 is the range-safe narrow dtype on TPU)")
        offload_grad_dtype = jnp.dtype(gd_table[gd_raw])
        if config.prescale_gradients:
            # Reference predivide-then-SUM-allreduce (engine.py:2533) nets out
            # to the mean; SPMD grads here are already global means, so the
            # knob is accepted but has no additional effect.
            log_dist("prescale_gradients is subsumed by SPMD mean-reduction; ignoring")

        # compressed DP gradient reduction (comm/compressed.py): compute
        # PER-SHARD grads under shard_map and reduce them with the int8
        # two-stage all-reduce instead of letting SPMD insert the exact
        # fp32 psum. Pure-DP stage-0 only: sharded params (ZeRO 1-3), model
        # parallel axes, and MoE expert grads keep the exact path — their
        # reductions live inside the declarative program. With the knob off
        # this branch doesn't exist and the step is bit-identical to before.
        # fp16 is excluded: the quantizer's where(absmax > 0) maps NaN grads
        # to finite zeros, so an overflow would slip past the loss-scale
        # skip gate — the exact psum propagates NaN and skips correctly
        cc = config.compressed_collectives
        site_eligible = (config.zero_optimization.stage == 0
                         and topo.pp_size == 1 and topo.tp_size == 1
                         and topo.sp_size == 1 and not config.moe.enabled
                         and topo.dp_size > 1 and self._host_adam is None
                         and not fp16)
        # remembered for replan_dp_grad: the control plane must not claim
        # a re-plan on an engine whose reductions are declarative
        self._dp_grad_site_eligible = site_eligible
        dp_grad_impl = None  # (mode, block, hierarchical) when compressed
        if cc.mode != "none":  # raw knob explicitly set: it wins as before
            compressed_dp = cc.dp_gradients and site_eligible
            if cc.dp_gradients and not compressed_dp:
                log_dist("compressed_collectives: DP gradient site needs pure "
                         "data parallelism at ZeRO stage 0 without fp16 loss "
                         "scaling — keeping the exact reduction (ZeRO++/MoE/"
                         "Ulysses sites gate separately)")
            if compressed_dp:
                cc_hier = (cc.hierarchical and topo.ep_size > 1
                           and topo.dp_outer_size > 1)
                dp_grad_impl = (cc.mode, cc.block, cc_hier)
        else:
            # comm-planner dp-grad site: with no raw knob set, the planner
            # (mode static|measure) picks the reduction implementation per
            # mesh + message size; off keeps the exact psum (bit-identical)
            compressed_dp = False
            from ..comm.planner import planner_active, resolve_site
            if planner_active() and site_eligible:
                n_elems = sum(int(np.prod(p.shape)) if p.shape else 1
                              for p in jax.tree.leaves(self.state.params))
                d = resolve_site(op="all_reduce", shape=(n_elems,),
                                 dtype="float32", axes=topo.dp_axes,
                                 consumer="dp-grad")
                if d.impl == "program":
                    # planner-synthesized multi-phase program (the DCN
                    # shape: exact reduce-scatter over ICI, int8+error-
                    # feedback all-reduce over the cross-slice axis,
                    # all-gather back) — executed per step by
                    # comm.compressed.run_collective_program. Fused phases
                    # (via="fused_matmul": the ICI hops riding between the
                    # backward matmuls' tile steps) get their compute
                    # descriptors bound to the REAL chunk sizes here, so
                    # the flight ring's per-hop detail and the doctor's
                    # divergence report name what actually moves
                    from ..comm.compressed import bind_fused_tiles
                    program = bind_fused_tiles(d.program, n_elems,
                                               dict(topo.mesh.shape))
                    dp_grad_impl = ("program", d.block or cc.block,
                                    program)
                    compressed_dp = True
                elif d.impl in ("int8", "int8_sr", "hierarchical"):
                    hier = (d.impl == "hierarchical" and topo.ep_size > 1
                            and topo.dp_outer_size > 1)
                    mode_ = "int8" if d.impl == "hierarchical" else d.impl
                    dp_grad_impl = (mode_, d.block or cc.block, hier)
                    compressed_dp = True
        if compressed_dp:
            mode_, block_, hier_ = dp_grad_impl
            if mode_ == "program":
                from ..comm.planner import program_summary
                fused_n = sum(1 for s in hier_
                              if getattr(s, "via", "xla") == "fused_matmul")
                log_dist(f"DP gradients ride a planner program: "
                         f"{program_summary(hier_)}"
                         + (f" ({fused_n} phase(s) fused into the "
                            f"producing/consuming matmul tiles)"
                            if fused_n else ""))
            else:
                log_dist(f"DP gradients ride the {mode_} all-reduce "
                         f"(block={block_}{', hierarchical' if hier_ else ''})")
        self._compressed_dp = compressed_dp  # imperative backward() reads it
        self._dp_grad_impl = dp_grad_impl

        # cross-step error-feedback residual for a program with an int8_ef
        # hop: engine-owned (TrainState.comm_feedback — global arrays with
        # the per-rank layout on the leading dp dim) so the GAS step carries
        # ONE residual across steps, snapshots include it, and rollback
        # restores the snapshot's copy instead of replaying a stale one
        fb = ()
        if dp_grad_impl is not None and dp_grad_impl[0] == "program":
            from ..comm.compressed import program_feedback_init

            # n_elems comes from the planner-resolution branch above — the
            # only producer of a program decision, so it is always bound here
            per_rank = program_feedback_init(n_elems, dp_grad_impl[2],
                                             dict(topo.mesh.shape))
            if per_rank is not None:
                fb_sh = NamedSharding(topo.mesh, P(topo.dp_axes))  # spec-ok: comm-feedback state is per-dp-rank
                fb = type(per_rank)(
                    worker_error=jax.device_put(
                        jnp.zeros((topo.dp_size,)
                                  + per_rank.worker_error.shape, jnp.float32),
                        fb_sh),
                    server_error=jax.device_put(
                        jnp.zeros((topo.dp_size,)
                                  + per_rank.server_error.shape, jnp.float32),
                        fb_sh))
        # () vs a 2-field NamedTuple: length check only, no array compares
        self._dp_feedback = fb != ()
        self.state = self.state.replace(comm_feedback=fb)

        def train_step(state: TrainState, batch, rng, *, ltd_keep=None,
                       moq_bits=None):
            scale = state.loss_scale.scale if fp16 else jnp.asarray(1.0, jnp.float32)

            def micro(carry, xs):
                acc = carry
                mb, mb_rng = xs

                def scaled_loss(p):
                    loss, aux = self._loss(p, mb, mb_rng, ltd_keep=ltd_keep,
                                           moq_bits=moq_bits)
                    return loss * scale, loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                grads = jax.lax.with_sharding_constraint(
                    grads, rules.shardings(self.grad_spec_tree))
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss

            rngs = jax.random.split(rng, gas)
            # trace-time read of the ATTRIBUTE (not the _compile-time local):
            # degraded mode flips it off and invalidates compiled steps, and
            # the retrace must land on the exact psum path
            if self._compressed_dp:
                grads, losses, new_fb = self._compressed_grad_phase(
                    state.params, batch, rngs, rng, scale,
                    feedback=(state.comm_feedback if self._dp_feedback
                              else None),
                    ltd_keep=ltd_keep, moq_bits=moq_bits)
            else:
                new_fb = None
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                zeros = jax.lax.with_sharding_constraint(zeros, rules.shardings(self.grad_spec_tree))
                acc, losses = lax.scan(micro, zeros, (batch, rngs))

                # unscale (+ average over gas; per-microbatch losses are
                # already global-batch means under SPMD — matches reference
                # GAS loss scaling, engine.py:2023)
                denom = scale * gas
                grads = jax.tree.map(lambda g: g / denom, acc)
            if self.frozen_patterns:
                # requires_grad=False semantics: frozen grads are zeroed
                # BEFORE the norm so clipping of trained params matches an
                # unfrozen-free run exactly (the optimizer masking alone
                # would leave them inflating grad_norm)
                grads = jax.tree.map(
                    lambda g, lbl: jnp.zeros_like(g) if lbl == "freeze" else g,
                    grads, self._frozen_labels)

            grad_norm = global_grad_norm(grads)
            overflow = ~jnp.isfinite(grad_norm) if fp16 else jnp.zeros([], jnp.bool_)
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)

            # bound once: the overflow select below must also see the
            # device copy — mixing a pinned-host leaf into compiled math is
            # the crash _opt_to_device exists to prevent
            opt_in = self._opt_to_device(state.opt_state)
            updates, new_opt = self.tx.update(grads, opt_in, state.params)
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
                state.params, updates)
            if fp16:
                new_params = _tree_where(overflow, state.params, new_params)
                new_opt = _tree_where(overflow, opt_in, new_opt)
            new_ls = update_loss_scale(
                state.loss_scale, overflow,
                dynamic=fp16_dynamic,
                scale_window=config.fp16.loss_scale_window,
                min_scale=config.fp16.min_loss_scale,
                max_hysteresis=config.fp16.hysteresis,
                consecutive_hysteresis=config.fp16.consecutive_hysteresis)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, loss_scale=new_ls,
                                   comm_feedback=(state.comm_feedback
                                                  if new_fb is None
                                                  else new_fb))
            metrics = {
                "loss": jnp.mean(losses),
                "grad_norm": grad_norm,
                "lr": jnp.asarray(self.lr_schedule(state.step + 1), jnp.float32),
                "loss_scale": state.loss_scale.scale,
                "overflow": overflow,
            }
            return new_state, metrics

        def grad_step(params, batch, rng, step, *, ltd_keep=None,
                      moq_bits=None):
            # ZeRO-Offload device half: grads + metrics only; the optimizer
            # update happens on host (engine._host_adam). fp16 loss scaling
            # is rejected at init in this mode (bf16/fp32 only), so the
            # micro scan needs no scale factor.
            def micro(carry, xs):
                acc = carry
                mb, mb_rng = xs
                loss, grads = jax.value_and_grad(
                    lambda p: self._loss(p, mb, mb_rng, ltd_keep=ltd_keep,
                                         moq_bits=moq_bits)[0]
                )(params)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                grads = jax.lax.with_sharding_constraint(
                    grads, rules.shardings(self.grad_spec_tree))
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zeros = jax.lax.with_sharding_constraint(zeros, rules.shardings(self.grad_spec_tree))
            rngs = jax.random.split(rng, gas)
            acc, losses = lax.scan(micro, zeros, (batch, rngs))
            grads = jax.tree.map(lambda g: g / gas, acc)
            if self.frozen_patterns:  # same masking as the fused step
                grads = jax.tree.map(
                    lambda g, lbl: jnp.zeros_like(g) if lbl == "freeze" else g,
                    grads, self._frozen_labels)
            grad_norm = global_grad_norm(grads)
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)
            if offload_grad_dtype != jnp.dtype(jnp.float32):
                # transport-dtype narrowing happens AFTER fp32 accumulation,
                # norm and clip — only the D2H bytes shrink (reference
                # ZeRO-Offload ships compute-dtype grads to the CPU optimizer)
                grads = jax.tree.map(
                    lambda g: g.astype(offload_grad_dtype), grads)
            metrics = {"loss": jnp.mean(losses), "grad_norm": grad_norm,
                       "lr": jnp.asarray(self.lr_schedule(step + 1), jnp.float32),
                       "loss_scale": jnp.asarray(1.0, jnp.float32),
                       "overflow": ~jnp.isfinite(grad_norm)}
            return grads, metrics

        state_sh = TrainState(
            step=NamedSharding(topo.mesh, P()),  # spec-ok: step counter replicates
            params=self._param_shardings,
            opt_state=self._opt_shardings,
            loss_scale=jax.tree.map(lambda _: NamedSharding(topo.mesh, P()), self.state.loss_scale),  # spec-ok: loss scale replicates
            comm_feedback=jax.tree.map(
                lambda _: NamedSharding(topo.mesh, P(topo.dp_axes)),  # spec-ok: comm-feedback state is per-dp-rank
                self.state.comm_feedback))

        if self._host_adam is not None:
            grad_sh = jax.tree.map(lambda s: NamedSharding(topo.mesh, s),
                                   self.grad_spec_tree,
                                   is_leaf=lambda x: isinstance(x, P))

            def make_train_step(ltd_keep, moq_bits=None):
                step = partial(grad_step, ltd_keep=ltd_keep,
                               moq_bits=moq_bits)
                step.__name__ = "grad_step"  # see train_step below
                return jax.jit(step,
                               in_shardings=(self._param_shardings, None, None, None),
                               out_shardings=(grad_sh, None))
        else:
            def make_train_step(ltd_keep, moq_bits=None):
                # one compiled program per (random-LTD stage, MoQ bit-width)
                # pair — both schedules quantize their steps, bounding the set
                step = partial(train_step, ltd_keep=ltd_keep,
                               moq_bits=moq_bits)
                # a partial has no name and jit would call the program
                # "<unknown>": profiler traces and IR dumps find the step
                # by this one (jit_train_step)
                step.__name__ = "train_step"
                return jax.jit(
                    step,
                    in_shardings=(state_sh, None, None),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,) if donate_state else ())

        self._make_train_step = make_train_step
        self._train_steps = {(None, None): make_train_step(None)}
        self._compile_finish(state_sh)

    def _compressed_grad_phase(self, params, batch, rngs, step_rng, scale,
                               *, feedback=None, ltd_keep=None,
                               moq_bits=None):
        """GAS scan + quantized mean all-reduce, per-shard under shard_map.

        The exact path lets SPMD insert fp32 psums where replicated params
        meet dp-sharded batches; here each dp rank accumulates LOCAL grads
        over the microbatch scan, flattens the whole tree into one vector
        (one collective per step, the flat-buffer transport of
        ``compression/onebit.py``), and reduces it with
        ``comm.compressed.quantized_all_reduce`` — int8 payloads + one-lane
        scales on the wire, ~3.5x fewer bytes than the psum pair. ``int8_sr``
        dithers the rounding so the compressed mean is unbiased. Returns
        (replicated fp32 grads — already unscaled and gas-averaged — and the
        per-micro global-mean losses).

        Semantics note: the reduction equal-weights the RANKS. A loss that
        normalizes by a data-dependent count (e.g. a ragged valid-token
        mask) is averaged as mean-of-per-rank-means here, while the exact
        SPMD path computes the global count-weighted mean — identical for
        the engine's fixed-shape microbatches, different when per-rank valid
        counts diverge (the same contract as ``compression/onebit.py``'s
        per-shard reduction).

        ``feedback`` (the engine-owned ``TrainState.comm_feedback`` — per-
        rank residuals stacked on a leading dp dim) rides the shard_map as
        an extra sharded operand when a program with an ``int8_ef`` hop is
        resolved; the per-shard slice feeds the reduction and the updated
        residual comes back out. Returns ``(grads, losses, new_feedback)``
        — ``new_feedback`` is ``None`` on the feedback-free paths."""
        from ..utils.shard_map_compat import shard_map_nocheck

        topo, gas = self.topo, self.gas
        dpaxes = topo.dp_axes
        sr_key = jax.random.fold_in(step_rng, 0x0151)
        fb_in = feedback if feedback else None  # () and None both mean "off"

        def accumulate(p, b_l, rngs_l):
            def micro_l(acc, xs):
                mb, mb_rng = xs

                def scaled_loss(pp):
                    loss, _ = self._loss(pp, mb, mb_rng, ltd_keep=ltd_keep,
                                         moq_bits=moq_bits)
                    return loss * scale, loss

                g, loss = jax.grad(scaled_loss, has_aux=True)(p)
                g = jax.tree.map(lambda t: t.astype(jnp.float32), g)
                return jax.tree.map(jnp.add, acc, g), loss

            zeros = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), p)
            acc, losses = lax.scan(micro_l, zeros, (b_l, rngs_l))
            return jax.tree.map(lambda g: g / (scale * gas), acc), losses

        if fb_in is None:
            def per_shard(p, b_l, rngs_l, k):
                acc, losses = accumulate(p, b_l, rngs_l)
                return (self._quantized_grad_reduce(acc, k)[0],
                        lax.pmean(losses, dpaxes))

            grads, losses = shard_map_nocheck(
                per_shard, topo.mesh,
                in_specs=(P(), P(None, dpaxes), P(), P()),  # spec-ok: shard_map wiring for the quantized-grad body
                out_specs=(P(), P()))(params, batch, rngs, sr_key)  # spec-ok: shard_map wiring for the quantized-grad body
            return grads, losses, None

        fb_spec = jax.tree.map(lambda _: P(dpaxes), fb_in)  # spec-ok: comm-feedback slices are per-dp-rank

        def per_shard_fb(p, b_l, rngs_l, k, fb_l):
            acc, losses = accumulate(p, b_l, rngs_l)
            fb0 = jax.tree.map(lambda t: t[0], fb_l)  # [1, n] -> [n]
            red, nfb = self._quantized_grad_reduce(acc, k, feedback=fb0)
            nfb = jax.tree.map(lambda t: t[None], nfb)
            return red, lax.pmean(losses, dpaxes), nfb

        return shard_map_nocheck(
            per_shard_fb, topo.mesh,
            in_specs=(P(), P(None, dpaxes), P(), P(), fb_spec),  # spec-ok: shard_map wiring for the feedback-carrying body
            out_specs=(P(), P(), fb_spec))(params, batch, rngs, sr_key, fb_in)  # spec-ok: shard_map wiring for the feedback-carrying body

    def _quantized_grad_reduce(self, grads, sr_key, feedback=None):
        """Flatten a per-shard fp32 grad tree into ONE vector (the
        flat-buffer transport — one collective per reduction, padding paid
        once), mean-reduce it with the resolved transport, unflatten.
        Called INSIDE shard_map over the dp axes; shared by the GAS-scan
        and imperative-backward() paths.

        Transports: flat ``quantized_all_reduce`` (int8/int8_sr), the
        legacy hand-wired two-level knob (inner ``ep`` exact, outer
        ``dp_outer`` quantized), or a planner-synthesized multi-phase
        PROGRAM (``run_collective_program`` — exact ICI reduce-scatter,
        int8+feedback DCN hop, ICI all-gather) when the decision carries
        one. Returns ``(grad_tree, new_feedback)``; ``new_feedback`` is
        ``None`` unless a program's ``int8_ef`` hop consumed ``feedback``."""
        from ..comm.compressed import (hierarchical_quantized_all_reduce,
                                       quantized_all_reduce,
                                       run_collective_program)

        mode_, block_, extra_ = self._dp_grad_impl  # knob- or planner-resolved
        flat, tdef = jax.tree.flatten(grads)
        sizes = [int(np.prod(g.shape)) for g in flat]
        shapes = [g.shape for g in flat]
        vec = jnp.concatenate([jnp.ravel(g) for g in flat])
        new_fb = None
        if mode_ == "program":
            red, new_fb = run_collective_program(vec, extra_,
                                                 feedback=feedback,
                                                 key=sr_key)
        else:
            sr = mode_ == "int8_sr"
            kw = dict(block=block_, stochastic=sr, key=sr_key if sr else None)
            if extra_:
                # inner (ICI-local) hop exact, only the outer hops quantize
                red = hierarchical_quantized_all_reduce(vec, "ep", "dp_outer",
                                                        **kw)
            else:
                red = quantized_all_reduce(vec, self.topo.dp_axes, **kw)
        offs = np.cumsum([0] + sizes)
        return jax.tree.unflatten(tdef, [
            red[offs[i]:offs[i + 1]].reshape(shapes[i])
            for i in range(len(sizes))]), new_fb

    def _compile_finish(self, state_sh):
        self._train_step = self._train_steps[(None, None)]
        self._aot_step = None  # (executable, batch fingerprint) from compile()
        # (key, batch fingerprint) -> measured AOT executable, filled when
        # telemetry.memory_analysis records each variant's compile-time
        # memory breakdown (a curriculum reshape is a new fingerprint)
        self._mem_execs = {}
        self._state_shardings = state_sh
        self._rng = jax.random.PRNGKey(self.config.seed)

    def _measured_exec(self, step_fn, key, batch, step_rng):
        """AOT-compile one train-step variant, record its
        ``memory_analysis()`` breakdown, and return the executable (which
        then serves matching steps — same program, same numerics)."""
        fp = (key, self._batch_fingerprint(batch))
        exe = self._mem_execs.get(fp)
        if exe is None:
            exe = step_fn.lower(self.state, batch, step_rng).compile()
            self._mem_execs[fp] = exe
            label = ("train_step" if key == (None, None)
                     else f"train_step{key}")
            self._record_memory_analysis(exe, label)
        return exe

    def _record_memory_analysis(self, exe, label: str) -> None:
        """Fold one compiled executable's ``memory_analysis()`` into the
        comms ledger's plan table and (when telemetry is live) the
        ``dstpu_mem_exec_bytes`` registry gauges. Best-effort: a backend
        without the surface records nothing."""
        try:
            ma = exe.memory_analysis()
        except Exception:
            return
        if ma is None:
            return
        info = {}
        for kind in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, kind, None)
            if v is not None:
                info[kind] = int(v)
        if not info:
            return
        dist.get_comms_logger().record_memory(label, info)
        if self.telemetry is not None:
            self.telemetry.record_memory_analysis(label, info)

    # ------------------------------------------------------------------
    # control-plane actuators (deepspeed_tpu/control/) + retrace plumbing
    # ------------------------------------------------------------------
    def invalidate_compiled_steps(self) -> None:
        """A trace-time constant changed (LR scale, remat policy, degraded
        collectives): drop every compiled step — and the measured AOT
        executables, which bake the same constants — so the next call
        retraces. State, specs, and the resolved dp-grad plan are kept."""
        self._train_steps = {(None, None): self._make_train_step(None)}
        self._train_step = self._train_steps[(None, None)]
        self._aot_step = None
        self._apply_fn = None
        self._micro_step_fn = None
        self._eval_fn = None
        self._mem_execs = {}

    def reconfigure_step(self) -> None:
        """A structural knob changed (gas/micro-batch split, a re-planned
        dp-grad transport): re-run ``_compile`` — plan resolution, feedback
        state, and step closures are all rebuilt against the CURRENT
        attributes — preserving the training RNG stream (``_compile_finish``
        reseeds it for fresh engines; a mid-run reconfigure must not replay
        step 0's randomness)."""
        rng = self._rng
        self._compile(self._donate_state)
        self._rng = rng
        self._apply_fn = None
        self._micro_step_fn = None
        self._eval_fn = None

    def raise_remat(self) -> Optional[str]:
        """Climb one rung of :data:`REMAT_LADDER` (the control plane's
        memory-pressure actuator). Returns the new policy name, or None
        when already at the top (nothing left to trade)."""
        cur = self._remat_policy
        if cur in REMAT_LADDER:
            idx = REMAT_LADDER.index(cur)
            if idx + 1 >= len(REMAT_LADDER):
                return None
            nxt = REMAT_LADDER[idx + 1]
        elif cur != REMAT_LADDER[-1]:
            nxt = REMAT_LADDER[-1]  # custom policy: escalate to full remat
        else:
            return None
        self._remat_policy = nxt
        self.invalidate_compiled_steps()
        log_dist(f"engine: remat policy raised to {nxt} (next step retraces)")
        return nxt

    def halve_micro_batch(self) -> bool:
        """Halve the per-device micro-batch and double GAS — the global
        batch, the optimizer schedule, and the training math are unchanged
        (the GAS scan equal-weights fixed-size microbatches); per-microbatch
        activation residency halves. The caller passes whole-step batches
        (``[gas * micro_global, ...]`` leaves reshape against the new gas
        automatically); a registered dataloader owns its own batch shape —
        the control policy skips this actuator there. Returns False when
        the micro-batch cannot halve (already 1 / odd) or a dataloader
        owns the batch shape."""
        if self._train_dataloader is not None:
            return False
        if self.micro_batch_size < 2 or self.micro_batch_size % 2:
            return False
        self.micro_batch_size //= 2
        self.gas *= 2
        cfg = self.config
        cfg.train_micro_batch_size_per_gpu = self.micro_batch_size
        cfg.gradient_accumulation_steps = self.gas
        # keep the batch triangle consistent for any later finalize()
        cfg._user_batch = (cfg.train_batch_size, self.micro_batch_size,
                           self.gas)
        self.reconfigure_step()
        log_dist(f"engine: micro-batch halved to {self.micro_batch_size} "
                 f"(gas {self.gas}); next step retraces")
        return True

    def replan_dp_grad(self, slow_axes, penalty: float = 4.0
                       ) -> Optional[str]:
        """Re-plan the DP-gradient collective around a slow link (the
        control plane's straggler actuator): the planner demotes
        ``slow_axes`` to penalized DCN-class links and re-synthesizes
        (``CollectivePlanner.replan_around``), then the step recompiles so
        the new transport — typically a hierarchical program whose
        full-width phases exclude the slow axes — takes effect. Returns
        the re-resolved plan summary, or None when the planner is off, no
        axis matched, or this engine has no re-plannable DP-grad site
        (ZeRO>0 / model-parallel / fp16 configurations keep their
        declarative reductions — a 'successful' re-plan there would be a
        lie the ledger then repeats)."""
        from ..comm.planner import (get_planner, planner_active,
                                    program_summary)

        if not planner_active() or not getattr(
                self, "_dp_grad_site_eligible", False):
            return None
        if not get_planner().replan_around(slow_axes, penalty=penalty):
            return None
        self.reconfigure_step()
        impl = self._dp_grad_impl
        if impl is None:
            return "exact-xla"
        return (program_summary(impl[2]) if impl[0] == "program"
                else impl[0])

    # ------------------------------------------------------------------
    # primary API
    # ------------------------------------------------------------------
    def train_batch(self, batch=None, data_iter: Optional[Iterable] = None):
        """Run one full training step: ``gas`` microbatches + optimizer update
        (reference ``PipelineEngine.train_batch`` / engine fwd-bwd-step loop).

        ``batch`` leaves are either ``[gas, micro_global, ...]`` or
        ``[gas * micro_global, ...]`` (reshaped automatically).
        """
        if self._no_sync_depth > 0:
            raise RuntimeError(
                "train_batch() applies the optimizer unconditionally and is "
                "incompatible with an open no_sync() context; use the "
                "imperative backward()/step() path inside no_sync()")
        if self._compat_count > 0:
            # reference accumulate-then-batch pattern (no_sync + backward,
            # then train_batch for the boundary step): the fused step would
            # silently DROP the accumulated micro-grads — fail loudly and
            # point at the migration instead
            raise RuntimeError(
                f"train_batch() called with {self._compat_count} accumulated "
                "microbatch gradient(s) pending from backward(); the fused "
                "step would drop them. Finish the window with backward()+"
                "step() (the no_sync migration), or discard via "
                "zero_grad() before switching to train_batch()")
        if self.telemetry is not None:
            # stamp BEFORE the draw so every span of this call — including
            # data/draw — carries the step about to execute
            self.telemetry.tracer.set_step(self.global_steps)
        if batch is None:
            with span("data/draw"):
                batch = _draw_from_iter(data_iter, self.gas)
        if self.resilience is not None:
            # arm the step watchdog AFTER the batch draw (the routine
            # epoch-end StopIteration must not leave a deadline armed over
            # whatever the caller does next) but BEFORE dispatch: the
            # deadline then covers dispatch plus every blocking device sync
            # post_step performs — the window a wedged collective actually
            # hangs in. Exceptions the caller handles (XLA errors, shape
            # mismatches) disarm via abort_step instead of leaving a live
            # deadline behind.
            self.resilience.pre_step()
            try:
                return self._train_batch_armed(batch)
            except BaseException as e:
                self.resilience.abort_step()
                self._crash_flight_dump(e)
                raise
        try:
            return self._train_batch_armed(batch)
        except BaseException as e:
            self._crash_flight_dump(e)
            raise

    def _crash_flight_dump(self, exc: BaseException) -> None:
        """Crash hook: an unhandled train-loop exception would otherwise
        lose the flight ring (the watchdog/rollback/drain dumps only cover
        *their* paths) — dump it with ``reason="crash"`` and the exception
        summary before the raise propagates. StopIteration is the routine
        epoch-end signal, not a crash; everything else (including injected
        faults and XLA errors) leaves a post-mortem."""
        if (self.telemetry is not None
                and isinstance(exc, Exception)
                and not isinstance(exc, StopIteration)):
            self.telemetry.crash_dump(exc)

    def _train_batch_armed(self, batch):
        """Telemetry shell around the step body: opens the per-step ``step``
        span and folds the window into the flight ring / phase histograms at
        the end. With telemetry off this is a single attribute check."""
        tm = self.telemetry
        if tm is None:
            return self._train_batch_inner(batch)
        # the step EXECUTING is the pre-increment number: the same N the
        # watchdog armed with, the spans are stamped with, and a hangdump
        # reports — the flight ring must agree with all three
        step = self.global_steps
        with span("step"):
            out = self._train_batch_inner(batch)
        # _metrics_host is whatever already synced (lazy) — this hook must
        # never force a device round trip of its own
        tm.on_step_end(
            step,
            step_time_s=self._step_times[-1] if self._step_times else None,
            metrics=self._metrics_host)
        return out

    def state_fingerprint(self, chunks: int = 8) -> str:
        """Hex digest of the full TrainState (params + optimizer state) via
        the integrity tier's jitted fingerprint kernel
        (``runtime/resilience/integrity.py``). DP-replicated state must
        agree BITWISE across ranks, so equal digests mean equal state.
        This is the synchronous forensic entry point for drills, tests,
        and operator debugging — the ``resilience.integrity:`` block runs
        the same kernel on a cadence with a one-step-delayed fetch
        instead, keeping the hot path sync-free."""
        from .resilience.integrity import (fingerprint_hex,
                                           make_fingerprint_fn)

        fns = getattr(self, "_fp_fns", None)
        if fns is None:
            fns = self._fp_fns = {}
        fn = fns.get(chunks)
        if fn is None:
            fn = fns[chunks] = make_fingerprint_fn(chunks)
        return fingerprint_hex(np.asarray(fn(self.state)))

    def _train_batch_inner(self, batch):
        """The body of ``train_batch`` from batch shaping through the
        resilience post-step hook; runs with the step watchdog armed when
        resilience is enabled (``train_batch`` handles arm/abort)."""
        with span("data/shape"):
            batch = self._shape_batch(batch)
        if self.curriculum_scheduler is not None:
            # seqlen curriculum: truncate [gas, micro, seq] leaves to the
            # current difficulty. Each distinct difficulty is one recompile;
            # the scheduler's difficulty_step quantization bounds that set.
            diff = self.curriculum_scheduler.update_difficulty(self.global_steps)
            if self.curriculum_scheduler.curriculum_type == "seqlen":
                batch = jax.tree.map(
                    lambda x: x[:, :, :diff] if x.ndim >= 3 else x, batch)
        ltd_keep = None
        if self.random_ltd_scheduler is not None and self._loss_takes_ltd:
            ltd_keep = self.random_ltd_scheduler.update(self.global_steps)
        self._last_batch = batch  # reference only; sliced lazily by flops_profile
        self._rng, step_rng = jax.random.split(self._rng)
        # the integrity tier's shadow-step replay re-executes THIS step from
        # a retained pre-step state; the exact rng and step-fn cache key are
        # the rest of the recipe (runtime/resilience/integrity.py)
        self._last_step_rng = step_rng
        moq_bits = self.moq.update(self.global_steps) if self.moq else None
        if moq_bits is not None and moq_bits >= 16:
            moq_bits = None  # schedule_offset warmup: unquantized program
        executing_step = self.global_steps  # pre-increment: the N every
        # other post-mortem surface (spans, flight ring, watchdog) stamps
        key = (ltd_keep, moq_bits)
        self._last_step_key = key
        step_fn = self._train_steps.get(key)
        if step_fn is None:
            step_fn = self._train_steps[key] = self._make_train_step(
                ltd_keep, moq_bits)
        if (key == (None, None) and self._aot_step is not None
                and self._aot_step[1] == self._batch_fingerprint(batch)):
            step_fn = self._aot_step[0]  # AOT executable from compile()
        elif (self.telemetry is not None
              and self.telemetry.cfg.memory_analysis
              and self._host_adam is None):
            # telemetry.memory_analysis: AOT-compile this variant once so
            # its compile-time memory breakdown is recorded, then step
            # through the measured executable (the compile is paid once —
            # lower().compile() does not share the jit dispatch cache)
            step_fn = self._measured_exec(step_fn, key, batch, step_rng)
        t0 = time.perf_counter()
        with span("compute/dispatch"):
            if self._host_adam is not None:
                metrics = self._host_offload_step(step_fn, batch, step_rng)
            else:
                self.state, metrics = step_fn(self.state, batch, step_rng)
        if self.global_steps == 0 and self.config.memory_breakdown:
            self._log_memory_breakdown(step_fn, batch, step_rng)
        self.global_steps += 1
        if self.telemetry is not None and \
                self.telemetry.drain_due(self.global_steps):
            # once-per-window device drain: the span timeline gets one
            # interval that covers the step's actual device work (fwd/bwd,
            # grad reduce, optimizer all live inside the compiled program)
            # without paying a per-step pipeline stall
            with span("compute/drain"):
                jax.block_until_ready(metrics)  # sync-ok: opt-in windowed drain
        # Metrics stay on device; ``_last_metrics`` converts lazily. A per-step
        # device->host sync here would serialize the async dispatch
        # pipeline. Overflow-skip accounting is a device-side counter for
        # the same reason.
        self._metrics_dev = metrics
        self._metrics_host = None
        if self.fp16:
            self._skipped_dev = self._skipped_dev + metrics["overflow"].astype(jnp.int32)
        self._step_times.append(time.perf_counter() - t0)
        with span("metrics/report"):
            self._maybe_report()
        if self.resilience is not None:
            # fault injection -> preemption drain -> sentinel -> cadence
            # snapshot (runtime/resilience/supervisor.py). Not a hot-path
            # cost when disabled: the attribute is None and nothing runs.
            with span("resilience/post_step"):
                self.resilience.post_step()
        if self.control is not None:
            # supervisor policy: live signals -> flap-guarded knob actions
            # (deepspeed_tpu/control/). Runs AFTER the resilience hook so
            # it observes this step's rollback/health outcomes; host-only
            # work unless a fired rule actuates.
            with span("control/decide"):
                self.control.on_step(executing_step)
        at = self.config.autotuning
        if self.global_steps == at.end_profile_step:
            from ..autotuning.autotuner import AUTOTUNE_RESULT_ENV, report_autotune_result

            if os.environ.get(AUTOTUNE_RESULT_ENV):
                # steady-state only: skip the JIT-compile steps before
                # start_profile_step so compile time can't invert the ranking
                start = min(at.start_profile_step, at.end_profile_step - 1)
                times = self._step_times[max(0, start):]
                dt = float(np.mean(times)) if times else float("inf")
                report_autotune_result(self.train_batch_size / dt)
        return metrics["loss"]

    def _host_offload_step(self, step_fn, batch, step_rng):
        """ZeRO-Offload step: device grads → host SIMD Adam → device params.

        D2H transfers are started async for every leaf so they overlap the
        per-leaf kernel work; the update itself runs in the native library's
        thread pool (csrc/adam/cpu_adam.cpp). The fp32 master and moments
        never exist on device — only compute-dtype params and fp32 grads do.
        """
        state = self.state
        grads, metrics = step_fn(state.params, batch, step_rng, state.step)
        for leaf in jax.tree.leaves(grads):
            leaf.copy_to_host_async()
        self._apply_host_adam(grads, float(np.asarray(metrics["grad_norm"])),
                              already_clipped=True)
        return metrics

    def _apply_host_adam(self, grads, grad_norm: float,
                         already_clipped: bool = False):
        """Shared host-optimizer apply for train_batch and the compat step():
        finite check (skip on overflow), clip, lr lookup, native Adam, and
        the device upload of the new compute-dtype params."""
        state = self.state
        if not np.isfinite(grad_norm):
            self.state = state.replace(step=state.step + 1)
            return
        if not already_clipped:
            clip = self.config.gradient_clipping
            if clip and clip > 0:
                coef = min(1.0, clip / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)
        lr_t = float(np.asarray(self.lr_schedule(self.global_steps + 1)))
        emit_bf16 = jnp.dtype(self.compute_dtype) == jnp.dtype(jnp.bfloat16)
        # sync-ok: ZeRO-Offload host optimizer step (opt-in offload path)
        new_np = self._host_adam.step(jax.device_get(grads), lr=lr_t,
                                      emit_bf16=emit_bf16)
        new_params = jax.device_put(new_np, self._param_shardings)
        self.state = TrainState(step=state.step + 1, params=new_params,
                                opt_state=(), loss_scale=state.loss_scale,
                                comm_feedback=state.comm_feedback)

    def _log_memory_breakdown(self, step_fn, batch, step_rng):
        """Step-1 memory report (reference ``see_memory_usage`` at the first
        step + ``memory_breakdown``): live device/host stats plus the
        compiled train step's XLA accounting (cache-hit lowering)."""
        from ..utils.memory import compiled_memory_analysis, see_memory_usage

        see_memory_usage("after first train step", force=True)
        if self._host_adam is not None:
            analysis = compiled_memory_analysis(step_fn, self.state.params,
                                                batch, step_rng, self.state.step)
        else:
            analysis = compiled_memory_analysis(step_fn, self.state, batch, step_rng)
        if analysis:
            log_dist("compiled train step memory: " +
                     "  ".join(f"{k}={v:.3f}" for k, v in analysis.items()))
        self._memory_analysis = analysis

    def memory_breakdown(self):
        """Programmatic access to the step-1 XLA memory analysis (None until
        the first step runs with config.memory_breakdown enabled)."""
        return getattr(self, "_memory_analysis", None)

    def eval_batch(self, batch, compute_loss: bool = True):
        if self._eval_fn is None:
            def eval_step(state, mb, rng):
                loss, aux = self._loss(state.params, mb, rng)
                return loss

            self._eval_fn = jax.jit(eval_step,
                                    in_shardings=(self._state_shardings, None, None))
        self._rng, r = jax.random.split(self._rng)
        return float(np.asarray(self._eval_fn(self.state, batch, r)))

    # ------------------------------------------------------------------
    # reference-compat imperative API: forward -> backward (xGAS) -> step
    # ------------------------------------------------------------------
    def _run_micro_step(self, batch):
        """One fused value-and-grad microbatch pass, returning the would-be
        new accumulator + the unscaled loss."""
        if self._micro_step_fn is None:
            def micro_step(state, acc, mb, rng):
                scale = state.loss_scale.scale if self.fp16 else jnp.asarray(1.0, jnp.float32)

                def scaled_loss(p):
                    l, aux = self._loss(p, mb, rng)
                    return l * scale, l

                if self._compressed_dp:
                    # imperative half of the compressed DP wiring: this
                    # microbatch's per-shard grads ride the int8 all-reduce
                    # (the site excludes fp16, so scale == 1 and the
                    # accumulator contract is unchanged)
                    grads, loss = self._compressed_micro_grads(
                        state.params, mb, rng)
                else:
                    grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params)
                    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss

            self._micro_step_fn = jax.jit(micro_step)
        if self._compat_acc is None:
            self._compat_acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                            self.state.params)
        self._rng, r = jax.random.split(self._rng)
        return self._micro_step_fn(self.state, self._compat_acc, batch, r)

    def _compressed_micro_grads(self, params, mb, rng):
        """Imperative ``backward()`` analogue of ``_compressed_grad_phase``:
        ONE microbatch's per-shard grads, mean-reduced through the shared
        ``_quantized_grad_reduce`` flat-buffer transport. Same rank-mean
        semantics note as the GAS-scan path applies."""
        from ..utils.shard_map_compat import shard_map_nocheck

        dpaxes = self.topo.dp_axes

        def per_shard(p, mb_l, r):
            def loss_fn(pp):
                l, _ = self._loss(pp, mb_l, r)
                return l, l

            g, loss = jax.grad(loss_fn, has_aux=True)(p)
            g = jax.tree.map(lambda t: t.astype(jnp.float32), g)
            # feedback=None: the compat micro path reduces per MICROBATCH —
            # a residual per micro would be a different (noisier) carry than
            # the fused step's one-per-step; a program's int8_ef hop runs as
            # plain int8 here
            return (self._quantized_grad_reduce(
                        g, jax.random.fold_in(r, 0x0151))[0],
                    lax.pmean(loss, dpaxes))

        return shard_map_nocheck(
            per_shard, self.topo.mesh,
            in_specs=(P(), P(dpaxes), P()),  # spec-ok: shard_map wiring for the eval body
            out_specs=(P(), P()))(params, mb, rng)  # spec-ok: shard_map wiring for the eval body

    def forward(self, batch):
        """Compute the loss for one microbatch (reference ``engine.forward:1848``).

        Fused with the gradient pass: functional autodiff would otherwise
        recompute this forward inside ``backward()``, silently doubling a
        ported reference loop's compute. The grads are cached and committed
        by ``backward()``; a forward that is never followed by backward pays
        for them — use ``eval_batch`` for inference-only evaluation.
        """
        self._compat_batch = batch
        acc, loss = self._run_micro_step(batch)
        self._compat_pending = (acc, loss)
        return float(np.asarray(loss))

    def backward(self, loss=None, batch=None):
        """Accumulate grads for one microbatch (reference ``backward:2007``).
        ``loss`` is accepted for API compatibility; the grads cached by the
        fused ``forward`` are committed (or recomputed for an explicitly
        different ``batch``)."""
        if batch is not None and batch is not self._compat_batch:
            self._compat_pending = None  # different data: recompute
            self._compat_batch = batch
        if self._compat_batch is None:
            raise ValueError("backward() needs a microbatch: call forward(batch) first or "
                             "pass backward(batch=...) — grads are recomputed functionally, "
                             "a bare loss tensor is not enough on TPU")
        if self._compat_pending is None:
            self._compat_pending = self._run_micro_step(self._compat_batch)
        acc, loss_dev = self._compat_pending
        self._compat_acc = acc
        self._compat_pending = None
        self._compat_count += 1
        return float(np.asarray(loss_dev))

    @contextlib.contextmanager
    def no_sync(self):
        """Context manager suppressing the optimizer boundary while inside
        (reference ``engine.no_sync:1987``: skip gradient allreduce during
        accumulation micro-steps).

        On TPU the reduction itself is XLA's to schedule: the compiled
        ``train_batch`` GAS scan already accumulates before reducing, and the
        imperative ``backward()`` path's per-microbatch psum is inserted by
        SPMD where the grads are consumed. What the reference contract
        guarantees — and what this enforces — is that no optimizer step can
        fire on the imperative path while the context is open:
        ``is_gradient_accumulation_boundary`` reports False inside, so
        micro-steps keep accumulating regardless of
        ``gradient_accumulation_steps``. ``train_batch`` (a fused
        microbatch-scan + apply) is incompatible with an open context and
        raises.
        """
        self._no_sync_depth += 1
        try:
            yield
        finally:
            self._no_sync_depth -= 1

    def is_gradient_accumulation_boundary(self) -> bool:
        if self._no_sync_depth > 0:
            return False
        return self._compat_count >= self.gas

    def step(self):
        """Apply the optimizer with accumulated grads (reference ``step:2204``);
        no-op until the accumulation boundary like the reference."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._host_adam is not None:
            # route the accumulated grads through the host optimizer (the
            # jitted apply_step below assumes on-device optax state)
            grads = jax.tree.map(lambda g: g / self.gas, self._compat_acc)
            self._apply_host_adam(grads, float(np.asarray(global_grad_norm(grads))))
            self._compat_acc = None
            self._compat_count = 0
            # a forward() cached before this step holds grads computed
            # against the pre-step params/accumulator — drop it so a later
            # backward() cannot commit already-applied gradients
            self._compat_pending = None
            self.global_steps += 1
            return
        if self._apply_fn is None:
            config = self.config
            clip = config.gradient_clipping

            def apply_step(state, acc):
                scale = state.loss_scale.scale if self.fp16 else jnp.asarray(1.0, jnp.float32)
                grads = jax.tree.map(lambda g: g / (scale * self.gas), acc)
                grad_norm = global_grad_norm(grads)
                overflow = ~jnp.isfinite(grad_norm) if self.fp16 else jnp.zeros([], jnp.bool_)
                if clip and clip > 0:
                    coef = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                    grads = jax.tree.map(lambda g: g * coef, grads)
                opt_in = self._opt_to_device(state.opt_state)
                updates, new_opt = self.tx.update(grads, opt_in, state.params)
                new_params = jax.tree.map(
                    lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype),
                    state.params, updates)
                if self.fp16:
                    new_params = _tree_where(overflow, state.params, new_params)
                    new_opt = _tree_where(overflow, opt_in, new_opt)
                new_ls = update_loss_scale(state.loss_scale, overflow,
                                           dynamic=self.fp16 and config.fp16.loss_scale == 0,
                                           scale_window=config.fp16.loss_scale_window,
                                           min_scale=config.fp16.min_loss_scale,
                                           max_hysteresis=config.fp16.hysteresis)
                return TrainState(step=state.step + 1, params=new_params,
                                  opt_state=new_opt, loss_scale=new_ls,
                                  comm_feedback=state.comm_feedback)

            # out_shardings keep the optimizer state's memory kind (pinned
            # host under the offload storage tier) across compat steps
            self._apply_fn = jax.jit(apply_step, donate_argnums=(1,),
                                     out_shardings=self._state_shardings)
        self.state = self._apply_fn(self.state, self._compat_acc)
        self._compat_acc = None
        self._compat_count = 0
        self._compat_pending = None  # see host-adam branch above
        self.global_steps += 1

    def compile(self, example_batch=None, backend: str = "xla",
                compile_kwargs=None):
        """Ahead-of-time compile of the fused train step (reference
        ``engine.compile``, ``runtime/engine.py:3696``; there the model is
        re-wrapped in torch.compile — here jit is already the execution
        model, so this EAGERLY lowers+compiles so the first ``train_batch``
        pays no JIT cost inside the loop). ``backend``/``compile_kwargs``
        are accepted for signature parity; only "xla" exists on TPU."""
        if isinstance(example_batch, str):
            # reference signature compile(backend, compile_kwargs)
            # (engine.py:3696): a string first positional arg IS the backend,
            # not an example batch — shift the arguments accordingly
            if compile_kwargs is None and not isinstance(backend, str):
                compile_kwargs = backend
            backend = example_batch
            example_batch = None
        if backend != "xla":
            log_dist(f"compile backend {backend!r} ignored: XLA is the only "
                     "execution model on TPU")
        if example_batch is None:
            return self  # nothing to shape the lowering with; lazy JIT stands
        batch = self._shape_batch(example_batch)
        rng = jax.random.PRNGKey(0)
        # keep the executable and route matching train_batch calls through
        # it — lower().compile() does NOT warm the jit dispatch cache, so
        # discarding it would pay the 20-40s JIT twice. trace() is the
        # same staging pipeline lower() runs internally; keeping the
        # Traced around gives the static auditor the jaxpr for free.
        if self._host_adam is not None:
            traced = self._train_step.trace(self.state.params, batch, rng,
                                            self.state.step)
        else:
            traced = self._train_step.trace(self.state, batch, rng)
        lowered = traced.lower()
        exe = lowered.compile()
        self._aot_step = (exe, self._batch_fingerprint(batch))
        # the AOT path holds a real executable: its compile-time memory
        # breakdown is free — record it in the plan table + registry
        self._record_memory_analysis(exe, "train_step")
        self._run_static_audit(traced, exe, "train_step", lowered=lowered)
        return self

    def _run_static_audit(self, traced, compiled, label: str, lowered=None):
        """Compile-time static audit (``deepspeed_tpu/analysis``, gated on
        the ``analysis:`` config block): reconcile the compiled program's
        collectives against the plan table / comms ledger / jaxpr, check
        precision, donation, and host-sync hazards — all on the already-
        staged objects, so the audit costs an HLO walk, not a recompile.
        Findings land in the ledger's plan table, ``Analysis/*`` monitor
        events, the telemetry registry, and (when a report dir is known)
        ``audit-report.json`` beside the resilience dumps so the doctor
        can cross-reference a hang against an unplanned collective."""
        acfg = self.config.analysis
        if not acfg.enabled:
            return None
        from ..analysis import AuditOptions, audit_step
        from ..analysis.report import REPORT_NAME, SEVERITIES

        if acfg.fail_on not in (None, "none") and acfg.fail_on not in SEVERITIES:
            # a typo'd threshold must not silently disable the gate the
            # user thinks is armed
            raise ConfigError(
                f"analysis.fail_on={acfg.fail_on!r}: use one of "
                f"{SEVERITIES} (or null for report-only)")

        opts = AuditOptions(
            small_bytes=acfg.small_bytes, big_bytes=acfg.big_bytes,
            precision_min_elems=acfg.precision_min_elems,
            precision_big_elems=acfg.precision_big_elems,
            donation_min_bytes=acfg.donation_min_bytes,
            collective_allowlist=tuple(acfg.collective_allowlist),
            precision_allowlist=tuple(acfg.precision_allowlist),
            strict=acfg.strict)
        ledger = dist.get_comms_logger()
        report = audit_step(traced, label=label, options=opts,
                            axis_sizes={str(k): int(v) for k, v in
                                        dict(self.topo.mesh.shape).items()},
                            plan_records=ledger.plan_records,
                            ledger=ledger, lowered=lowered,
                            compiled=compiled)
        counts = report.counts()
        summary = dict(counts)
        for key in ("hlo_collectives", "matched_collectives",
                    "unplanned_collectives", "unmatched_reductions"):
            if key in report.context:
                summary[key] = report.context[key]
        ledger.record_analysis(label, summary)
        if self.monitor is not None:
            step = self.global_steps
            events = [(f"Analysis/{label}/{sev}", counts[sev], step)
                      for sev in counts]
            events.append((f"Analysis/{label}/unplanned_collectives",
                           report.context.get("unplanned_collectives", 0),
                           step))
            self.monitor.write_events(events)
        if self.telemetry is not None:
            self.telemetry.count("analysis_findings", len(report.findings))
        report_dir = acfg.report_dir
        if report_dir is None and self.config.resilience.enabled:
            report_dir = self.config.resilience.snapshot_dir
        if report_dir:
            try:
                os.makedirs(report_dir, exist_ok=True)
                report.write(os.path.join(report_dir, REPORT_NAME))
            except OSError as e:
                log_dist(f"analysis: could not write {REPORT_NAME}: {e}")
        for line in report.render().splitlines():
            log_dist(f"analysis: {line}")
        if acfg.fail_on in SEVERITIES and report.at_or_above(acfg.fail_on):
            raise RuntimeError(
                f"static audit failed ({acfg.fail_on}+ findings present "
                f"and analysis.fail_on={acfg.fail_on!r}):\n"
                + report.render())
        return report

    @staticmethod
    def _batch_fingerprint(batch):
        return tuple((tuple(x.shape), jnp.dtype(x.dtype).name)
                     for x in jax.tree.leaves(batch))

    @property
    def is_compiled(self) -> bool:
        return True  # every executed step ran through XLA

    def zero_grad(self):
        """Discard accumulated compat-path micro-gradients (reference
        ``engine.zero_grad``). The fused ``train_batch`` manages its own
        accumulator, so this only matters when abandoning a
        ``backward()`` window, e.g. before switching back to
        ``train_batch``."""
        self._compat_acc = None
        self._compat_pending = None
        self._compat_count = 0

    # ------------------------------------------------------------------
    def _shape_batch(self, batch):
        gas = self.gas

        def reshape(x):
            x = jnp.asarray(x)
            if x.ndim >= 1 and x.shape[0] == gas:
                return x
            if x.shape[0] % gas != 0:
                raise ValueError(f"batch dim {x.shape[0]} not divisible by gas={gas}")
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        return jax.tree.map(reshape, batch)

    def _maybe_report(self):
        if self.global_steps % self.config.steps_per_print == 0:
            m = self._last_metrics
            log_dist(f"step={self.global_steps} loss={m.get('loss', float('nan')):.4f} "
                     f"lr={m.get('lr', 0):.3e} grad_norm={m.get('grad_norm', 0):.3f}")
        if self.monitor is not None:
            events = [
                (f"Train/Samples/train_loss", self._last_metrics.get("loss"),
                 self.global_steps * self.train_batch_size),
                (f"Train/Samples/lr", self._last_metrics.get("lr"),
                 self.global_steps * self.train_batch_size)]
            # ledger -> monitor bridge: per-op logical/wire bytes + latency
            # totals reach TensorBoard/CSV, not just stdout
            from ..comm import get_comms_logger
            ledger = get_comms_logger()
            if ledger.enabled:
                events += ledger.monitor_events(self.global_steps)
            # registry -> monitor bridge: the telemetry spine's counters and
            # phase histograms reach the existing JSONL/TB/W&B sinks too
            if (self.telemetry is not None
                    and self.telemetry.cfg.monitor_bridge):
                events += self.telemetry.registry.monitor_events(
                    self.global_steps)
            self.monitor.write_events(events)
        fp_cfg = self.config.flops_profiler
        if fp_cfg.enabled and self.global_steps == fp_cfg.profile_step:
            self.flops_profile(output_file=fp_cfg.output_file,
                               top_modules=fp_cfg.top_modules,
                               depth=fp_cfg.module_depth)

    def flops_profile(self, batch=None, output_file=None, top_modules: int = 3,
                      depth: int = -1):
        """Profile one microbatch's loss FLOPs per named scope (reference
        engine hook ``engine.py:1877`` → ``FlopsProfiler``). fwd+bwd+update
        FLOPs ≈ 3× the forward count reported here."""
        from ..profiling import FlopsProfiler

        prof = FlopsProfiler(self.config.flops_profiler)
        if batch is None and self._last_batch is not None:
            batch = jax.tree.map(lambda x: x[0], self._last_batch)
        if batch is None:
            logger.warning("flops_profile: no batch seen yet")
            return None
        self._rng, r = jax.random.split(self._rng)
        step_time = float(np.mean(self._step_times[-5:])) if self._step_times else 0.0
        prof.profile(lambda p, b: self._loss(p, b, r)[0],
                     (self.state.params, batch), params=self.state.params,
                     step_time=step_time)
        prof.print_model_profile(depth=depth, top_modules=top_modules,
                                 output_file=output_file)
        self.flops_profiler = prof
        return prof.total_flops

    # ------------------------------------------------------------------
    @property
    def _last_metrics(self) -> Dict[str, float]:
        """Host view of the latest step metrics (syncs on first access)."""
        if self._metrics_host is None:
            m = {k: float(np.asarray(v)) for k, v in self._metrics_dev.items()}
            if m.pop("overflow", 0.0):
                m["skipped"] = 1.0
            self._metrics_host = m
        return self._metrics_host

    @property
    def skipped_steps(self) -> int:
        """fp16 overflow-skipped step count (reference ``engine.skipped_steps``).
        Reads a device-side counter, so accessing it synchronizes."""
        return self._skipped_base + int(self._skipped_dev)

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        self._skipped_base = int(value)
        self._skipped_dev = jnp.zeros([], jnp.int32)

    @property
    def loss_scale(self) -> float:
        return float(np.asarray(self.state.loss_scale.scale))

    def get_lr(self):
        return [float(np.asarray(self.lr_schedule(self.state.step)))]

    def get_global_grad_norm(self) -> float:
        return self._last_metrics.get("grad_norm", 0.0)

    def zero_stage(self) -> int:
        return self.rules.stage

    def throughput(self) -> Dict[str, float]:
        """samples/sec + step latency (reference ``ThroughputTimer``,
        ``utils/timer.py:199``)."""
        if not self._step_times:
            return {}
        recent = self._step_times[-20:]
        dt = float(np.mean(recent))
        return {"step_time_s": dt, "samples_per_sec": self.train_batch_size / dt}

    # state offload (reference ``engine.offload_states:3720``) ----------
    def offload_states(self, include=("optimizer_state",), device: str = "cpu",
                       nvme_path: Optional[str] = None):
        """Move engine state off-device between training phases: ``cpu`` =
        host RAM (numpy), ``nvme`` = SSD via the native aio swap tier
        (``runtime/zero/swapper.py``). Training is invalid until
        ``reload_states`` — same contract as the reference."""
        self._offloaded = getattr(self, "_offloaded", {})
        for raw_kind in include:
            kind = self._canonical_kind(raw_kind)
            if kind in self._offloaded:
                continue
            tree, sh = self._state_part(kind)
            if device == "nvme":
                sw = self._get_swapper(nvme_path)
                sw.swap_out(kind, tree)
                sw.synchronize(kind)
                placeholder = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
                self._set_state_part(kind, placeholder)
                # keep the owning swapper with the entry: its in-memory
                # manifest is the only way back to this data
                self._offloaded[kind] = ("nvme", sh, sw)
            else:
                host_tree, _ = _to_host_memory(tree, sh, fallback="numpy")
                self._set_state_part(kind, host_tree)
                self._offloaded[kind] = ("cpu", sh, None)

    def reload_states(self):
        for kind, (where, sh, sw) in list(getattr(self, "_offloaded", {}).items()):
            if where == "nvme":
                tree = sw.swap_in(kind, shardings=sh, delete=True)
            else:
                tree, _ = self._state_part(kind)
                tree = jax.device_put(tree, sh)
            self._set_state_part(kind, tree)
            del self._offloaded[kind]

    @staticmethod
    def _canonical_kind(kind: str) -> str:
        if kind in ("optimizer_state", "optimizer"):
            return "optimizer_state"
        if kind in ("params", "fp32_params", "hp_params"):
            return "params"
        raise ValueError(f"unknown offload kind {kind!r} "
                         "(use 'optimizer_state' or 'params')")

    def _state_part(self, kind: str):
        if kind == "optimizer_state":
            return self.state.opt_state, self._opt_shardings
        return self.state.params, self._param_shardings

    def _set_state_part(self, kind: str, tree):
        if kind == "optimizer_state":
            self.state = self.state.replace(opt_state=tree)
        else:
            self.state = self.state.replace(params=tree)

    def _get_swapper(self, nvme_path: Optional[str]):
        path = nvme_path or self.config.zero_optimization.offload_optimizer.nvme_path
        if not path:
            raise ValueError(
                "offload to nvme needs a path: pass nvme_path= or set "
                "zero_optimization.offload_optimizer.nvme_path in the config")
        swappers = getattr(self, "_swappers", None)
        if swappers is None:
            swappers = self._swappers = {}
        if path not in swappers:
            from .zero.swapper import AsyncTensorSwapper

            aio = self.config.aio
            swappers[path] = AsyncTensorSwapper(
                os.path.join(path, "dstpu_swap"),
                num_threads=aio.thread_count, block_size=aio.block_size)
        return swappers[path]

    def should_stop(self) -> bool:
        """True once the resilience tier drained for a preemption: the final
        snapshot is durable and the training loop should exit so the grace
        window is not spent on steps that will be lost."""
        r = self.resilience
        return bool(r is not None and r.stop_requested)

    # checkpointing (delegates to checkpoint subsystem) -----------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None, **kw):
        from ..checkpoint.engine import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state, **kw)

    def load_checkpoint(self, load_dir, tag=None, **kw):
        from ..checkpoint.engine import load_checkpoint as _load

        return _load(self, load_dir, tag=tag, **kw)


# ---------------------------------------------------------------------------


def _accepts_kw(fn, name: str) -> bool:
    try:
        sig = inspect.signature(fn)
        return name in sig.parameters or any(
            p.kind == p.VAR_KEYWORD for p in sig.parameters.values())
    except (TypeError, ValueError):
        return False


def _accepts_rng(fn) -> bool:
    try:
        sig = inspect.signature(fn)
        n_positional = sum(1 for p in sig.parameters.values()
                           if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
        return n_positional >= 3 or any(p.name in ("rng", "rngs", "key")
                                        for p in sig.parameters.values())
    except (TypeError, ValueError):
        return False


def _draw_from_iter(data_iter, gas):
    mbs = [next(data_iter) for _ in range(gas)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *mbs)


_HOST_JIT_PROBE: Dict[Any, bool] = {}


def _host_memory_jit_supported(mesh) -> bool:
    """Whether COMPILED programs on this mesh can take/return pinned-host
    operands (the memories API). TPU yes; the multi-device CPU SPMD
    partitioner rejects the placement annotations ('side-effect ops cannot
    be replicated'), so the offload storage tier must probe before placing
    optimizer state in host memory — host-resident inputs to a jit that
    cannot express them would crash the first train step."""
    # stable key (id() could be recycled after GC): platform + device ids
    key = (mesh.devices.flat[0].platform,
           tuple(d.id for d in mesh.devices.flat))
    if key not in _HOST_JIT_PROBE:
        try:
            sh = NamedSharding(mesh, P()).with_memory_kind("pinned_host")  # spec-ok: pinned-host capability probe, single scalar
            x = jax.device_put(jnp.zeros((1,), jnp.float32), sh)
            jax.jit(lambda v: v + 1, in_shardings=sh, out_shardings=sh)(x)
            _HOST_JIT_PROBE[key] = True
        except Exception:
            _HOST_JIT_PROBE[key] = False
    return _HOST_JIT_PROBE[key]


def _to_host_memory(tree, shardings, fallback: str = "keep"):
    """Move a pytree to pinned host memory (ZeRO-Offload tier; reference
    ``offload_optimizer.device=cpu``). Returns ``(tree, shardings)`` with the
    shardings updated to the actual residency, so later device_puts (e.g.
    ``reload_states``) restore the same memory kind. When the backend has no
    pinned_host space: ``fallback='keep'`` leaves the leaf on device,
    ``'numpy'`` fetches it to host RAM."""
    flat, treedef = jax.tree.flatten(tree)
    shs = jax.tree.leaves(shardings)
    out_leaves, out_shs = [], []
    for x, sh in zip(flat, shs):
        try:
            host_sh = sh.with_memory_kind("pinned_host")
            out_leaves.append(jax.device_put(x, host_sh))
            out_shs.append(host_sh)
        except Exception:
            # sync-ok: offload fallback when pinned-host memory is absent
            out_leaves.append(x if fallback == "keep" else jax.device_get(x))
            out_shs.append(sh)
    return (jax.tree.unflatten(treedef, out_leaves),
            jax.tree.unflatten(treedef, out_shs))


def initialize(args=None,
               model: Optional[Callable] = None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=None,
               mpu=None,
               dist_init_required=None,
               config=None,
               config_params=None,
               topology: Optional[Topology] = None,
               param_specs=None,
               batch_spec=None,
               **kwargs):
    """Create an engine (reference ``deepspeed.initialize``,
    ``deepspeed/__init__.py:69``; same signature vocabulary).

    ``model`` is a pure loss function ``loss = f(params, batch[, rng])`` or a
    flax module whose ``apply`` returns the loss; ``model_parameters`` is the
    initial parameter pytree (fp32) — or, for the ``zero.Init`` analogue
    (shard-at-creation, reference ``partition_parameters.py:816``), a
    zero-arg closure returning that pytree (e.g.
    ``lambda: flax_model.init(key, dummy)["params"]``): each leaf then
    materializes directly into its ZeRO shard and no full-size copy of the
    model ever exists on host or any single device.
    Returns ``(engine, optimizer_proxy, dataloader, lr_scheduler_proxy)`` to
    match the reference tuple.
    """
    raw_cfg = config if config is not None else config_params
    from ..autotuning.autotuner import AUTOTUNE_CONFIG_ENV

    if os.environ.get(AUTOTUNE_CONFIG_ENV) and raw_cfg is not None:
        from ..autotuning.autotuner import apply_autotune_env_overrides

        if isinstance(raw_cfg, str):  # config file path: load, then overlay
            with open(raw_cfg) as f:
                raw_cfg = json.load(f)
        elif not isinstance(raw_cfg, dict):  # typed config object
            raw_cfg = raw_cfg.to_dict()
        raw_cfg = apply_autotune_env_overrides(raw_cfg)
    cfg = load_config(raw_cfg)
    dist.init_distributed()
    if topology is None:
        spec = TopologySpec(pp=cfg.pipeline.stages if cfg.pipeline.stages else 1,
                            ep=cfg.moe.ep_size if cfg.moe.enabled else 1,
                            sp=cfg.sequence_parallel_size,
                            tp=cfg.tensor_parallel.tp_size if cfg.tensor_parallel.enabled else 1)
        topology = Topology(spec)
    set_topology(topology)
    # latency-hiding collective matmul: the runtime knob flips the fleet-wide
    # default the model wiring reads (model configs can also opt in per-model
    # via TransformerConfig.overlap_collective_matmul)
    from ..ops.collective_matmul import set_overlap_enabled
    set_overlap_enabled(bool(cfg.tensor_parallel.overlap_collective_matmul))

    loss_fn = model
    if hasattr(model, "apply") and hasattr(model, "init"):  # flax module
        mod = model

        def loss_fn(params, batch, rng=None):
            kw = {"rngs": {"dropout": rng}} if rng is not None else {}
            return mod.apply({"params": params}, batch, **kw)

        from ..models.transformer import TransformerLM
        # TransformerLM reads the topology itself; any other flax module is
        # a foreign model and must bring specs when tp > 1 (the engine
        # raises ForeignModelShardingError instead of replicating densely)
        loss_fn._sharding_native = isinstance(mod, TransformerLM)

    engine = DeepSpeedTPUEngine(loss_fn=loss_fn, params=model_parameters, config=cfg,
                                topology=topology, param_specs=param_specs,
                                batch_spec=batch_spec, optimizer=optimizer,
                                lr_scheduler=lr_scheduler,
                                donate_state=kwargs.get("donate_state", True),
                                autotp_example_batch=kwargs.get(
                                    "autotp_example_batch"),
                                frozen_params=kwargs.get("frozen_params"))
    dist.configure(comms_logger=cfg.comms_logger)

    dataloader = None
    if training_data is not None:
        from .data_pipeline.data_sampler import build_curriculum_sampler
        from .dataloader import DeepSpeedDataLoader

        # metric-file-driven curriculum selection (DataAnalyzer outputs →
        # DeepSpeedDataSampler; reference deepspeed_io + data_sampler.py).
        # Selection happens at the loader; the engine's seqlen hook still
        # truncates independently when a seqlen curriculum is configured.
        sampler = None
        if cfg.data_efficiency.enabled:
            sampler = build_curriculum_sampler(
                cfg.data_efficiency.data_sampling,
                batch_size=cfg.train_micro_batch_size_per_gpu,
                seed=cfg.data_efficiency.seed,
                draws_per_opt_step=engine.gas)
            if sampler is not None and sampler.n_samples != len(training_data):
                raise ConfigError(
                    f"curriculum metric files cover {sampler.n_samples} "
                    f"samples but training_data has {len(training_data)} — "
                    "the DataAnalyzer output must come from this corpus")
        engine.data_sampler = sampler  # checkpointed with the engine state
        dataloader = DeepSpeedDataLoader(training_data,
                                         batch_size=cfg.train_micro_batch_size_per_gpu,
                                         sampler=sampler)
    if dataloader is not None:
        # the control plane's halve_micro_batch actuator must not change
        # the engine's batch split while a fixed-shape loader feeds it
        engine._train_dataloader = dataloader
    if dataloader is not None and engine.resilience is not None:
        # resumable data stream: the loader's position rides in snapshot
        # meta, and a restore (which already happened at engine init)
        # fast-forwards it so the post-restore batch sequence matches an
        # uninterrupted run
        engine.resilience.register_dataloader(dataloader)
    return engine, engine.tx, dataloader, engine.lr_schedule
