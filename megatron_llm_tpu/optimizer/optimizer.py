"""Mixed-precision optimizer with fp32 master params, global-norm clipping,
inf/nan skip, and ZeRO-1 state sharding.

Reference: ``megatron/optimizer/optimizer.py`` (ABC :93-302,
MixedPrecisionOptimizer :384-466, Float16OptimizerWithFloat16Params
:469-696, FP32Optimizer :698-783), ``clip_grads.py:16-107``,
``distrib_optimizer.py`` (ZeRO-1).

Functional design: ``init(params) -> OptimizerState``;
``step(params, grads, state, lr, wd) -> (params, state, stats)``.
Everything runs inside the jitted train step; the loss-scale skip is a
``jnp.where`` select, not host control flow, so a skipped iteration costs
one fused update kernel and no recompilation (the reference does a host-side
``if found_inf`` after an allreduce sync, optimizer.py:408-466).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu import health
from megatron_llm_tpu.config import TrainConfig
from megatron_llm_tpu.optimizer.grad_scaler import (
    ConstantGradScaler,
    DynamicGradScaler,
    GradScalerState,
)


class OptimizerState(NamedTuple):
    step: jnp.ndarray
    master_params: Any          # fp32 copies when params are low precision, else None
    exp_avg: Any                # adam m   (or SGD momentum buffer)
    exp_avg_sq: Any             # adam v   (None for SGD)
    grad_scaler: GradScalerState


def map_param_trees(fn, state: Optional[OptimizerState]):
    """``fn`` over every params-shaped tree of an optimizer state (master
    weights and moments; None where the optimizer keeps none): a change of
    the parameters' layout or form that the state has to follow."""
    if state is None:
        return None
    return state._replace(
        master_params=fn(state.master_params),
        exp_avg=fn(state.exp_avg),
        exp_avg_sq=fn(state.exp_avg_sq),
    )


def _no_weight_decay(path, leaf) -> bool:
    """WD applies to matmul weights only — biases and norm scales are
    excluded (reference: _get_params_for_weight_decay_optimization in
    megatron/optimizer/__init__.py: no WD for biases / 1-D params)."""
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    if "bias" in names or "scale" in names or "lora_scale" in names:
        return True
    if any("norm" in str(n) for n in names):
        return True
    # embeddings do get WD in the reference (they're weight matrices)
    return False


def global_grad_norm(grads) -> jnp.ndarray:
    """L2 norm over the whole grad pytree (reference:
    clip_grad_norm_fp32, clip_grads.py:16-107 — the MP-group allreduce of
    the squared norm is implicit: the pytree is logically global under
    GSPMD, sharded leaves reduce across the mesh automatically)."""
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


class MegatronOptimizer:
    """Adam(W) / SGD with Megatron mixed-precision semantics."""

    def __init__(self, train_cfg: TrainConfig, params_dtype=jnp.float32):
        self.cfg = train_cfg
        self.params_dtype = params_dtype
        self.is_low_precision = params_dtype != jnp.float32
        # moments storage dtype (config.optimizer_state_dtype): bf16
        # halves state HBM + step traffic; the update math below always
        # upcasts to fp32, so only STORAGE precision changes
        self.state_dtype = (
            jnp.bfloat16 if train_cfg.optimizer_state_dtype == "bf16"
            else jnp.float32
        )
        # loss scaling: only for fp16 (bf16 trains unscaled) —
        # reference: optimizer/__init__.py:88-107
        if train_cfg.fp16:
            if train_cfg.loss_scale is not None:
                self.grad_scaler = ConstantGradScaler(train_cfg.loss_scale)
            else:
                self.grad_scaler = DynamicGradScaler(
                    initial_scale=train_cfg.initial_loss_scale,
                    min_scale=train_cfg.min_loss_scale,
                    growth_interval=train_cfg.loss_scale_window,
                    hysteresis=train_cfg.hysteresis,
                )
        else:
            self.grad_scaler = ConstantGradScaler(1.0)

    # ------------------------------------------------------------------
    def init(self, params) -> OptimizerState:
        sd = self.state_dtype
        zeros = lambda p: jnp.zeros_like(p, dtype=sd)
        master = (
            jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
            if self.is_low_precision
            else None
        )
        exp_avg = jax.tree_util.tree_map(zeros, params)
        exp_avg_sq = (
            jax.tree_util.tree_map(zeros, params)
            if self.cfg.optimizer == "adam"
            else None
        )
        state = OptimizerState(
            step=jnp.int32(0),
            master_params=master,
            exp_avg=exp_avg,
            exp_avg_sq=exp_avg_sq,
            grad_scaler=self.grad_scaler.init(),
        )
        # place the scalar leaves (step, grad-scaler state) replicated on
        # the active mesh: the jitted train step emits them that way, so a
        # fresh init that matches avoids a second trace/compile of the
        # whole fused step at iteration 2
        from megatron_llm_tpu.parallel import sharding as _sh

        mesh = _sh._mesh()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            state = state._replace(
                step=jax.device_put(state.step, rep),
                grad_scaler=jax.tree_util.tree_map(
                    lambda s: jax.device_put(s, rep), state.grad_scaler),
            )
        return state

    # ------------------------------------------------------------------
    def step(
        self,
        params,
        grads,
        state: OptimizerState,
        lr,
        weight_decay: Optional[float] = None,
        *,
        layer_stats: bool = False,
    ):
        """One optimizer step.  ``grads`` are the *scaled* grads in fp32
        (the train step multiplies the loss by the current scale).

        Returns (new_params, new_state, stats) with stats =
        {'grad_norm', 'found_inf', 'loss_scale'}; with ``layer_stats``
        also 'layer_stats': fixed-shape per-group [G] arrays from
        ``health.compute_layer_stats`` (grad norms over the unscaled
        pre-clip grads so they partition 'grad_norm'; update norms over
        the applied master delta, zero on an overflow-skipped step).
        """
        cfg = self.cfg
        wd = cfg.weight_decay if weight_decay is None else weight_decay
        scale = state.grad_scaler.scale
        inv_scale = 1.0 / scale

        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv_scale, grads
        )
        # global inf/nan consensus (reference: optimizer.py:384-466)
        finite = jnp.array(True)
        for g in jax.tree_util.tree_leaves(grads):
            finite &= jnp.all(jnp.isfinite(g))
        found_inf = ~finite

        # global-norm clip (reference: clip_grads.py:16-107)
        unclipped_grads = grads
        grad_norm = global_grad_norm(grads)
        if cfg.clip_grad > 0.0:
            clip_coeff = jnp.minimum(1.0, cfg.clip_grad / (grad_norm + 1.0e-6))
            grads = jax.tree_util.tree_map(lambda g: g * clip_coeff, grads)

        step = state.step + jnp.where(found_inf, 0, 1)
        masters = state.master_params if self.is_low_precision else params

        paths = [
            p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        wd_mask_leaves = [0.0 if _no_weight_decay(p, None) else wd for p in paths]
        treedef = jax.tree_util.tree_structure(params)
        wd_mask = jax.tree_util.tree_unflatten(treedef, wd_mask_leaves)

        if cfg.optimizer == "adam":
            b1, b2, eps = cfg.adam_beta1, cfg.adam_beta2, cfg.adam_eps
            t = step.astype(jnp.float32)
            bc1 = 1.0 - b1 ** t
            bc2 = 1.0 - b2 ** t

            def upd(m_old, v_old, g, p32, w):
                m = b1 * m_old.astype(jnp.float32) + (1.0 - b1) * g
                v = (b2 * v_old.astype(jnp.float32)
                     + (1.0 - b2) * jnp.square(g))
                update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                # AdamW decoupled weight decay (apex adam_w_mode default)
                new_p = p32 - lr * (update + w * p32)
                return m.astype(m_old.dtype), v.astype(v_old.dtype), new_p

            out = jax.tree_util.tree_map(
                upd, state.exp_avg, state.exp_avg_sq, grads, masters, wd_mask
            )
            new_m = jax.tree_util.tree_map(lambda o: o[0], out,
                                           is_leaf=lambda o: isinstance(o, tuple))
            new_v = jax.tree_util.tree_map(lambda o: o[1], out,
                                           is_leaf=lambda o: isinstance(o, tuple))
            new_masters = jax.tree_util.tree_map(lambda o: o[2], out,
                                                 is_leaf=lambda o: isinstance(o, tuple))
        elif cfg.optimizer == "sgd":
            mom = cfg.sgd_momentum

            def upd(buf_old, g, p32, w):
                g = g + w * p32
                buf = mom * buf_old.astype(jnp.float32) + g
                new_p = p32 - lr * buf
                return buf.astype(buf_old.dtype), new_p

            out = jax.tree_util.tree_map(upd, state.exp_avg, grads, masters, wd_mask)
            new_m = jax.tree_util.tree_map(lambda o: o[0], out,
                                           is_leaf=lambda o: isinstance(o, tuple))
            new_v = None
            new_masters = jax.tree_util.tree_map(lambda o: o[1], out,
                                                 is_leaf=lambda o: isinstance(o, tuple))
        else:
            raise ValueError(f"unknown optimizer {cfg.optimizer!r}")

        # inf/nan skip: keep the old state wholesale (reference skips the
        # whole step, training.py:445-447)
        keep = lambda new, old: jax.tree_util.tree_map(
            lambda n, o: jnp.where(found_inf, o, n), new, old
        )
        new_masters = keep(new_masters, masters)
        new_m = keep(new_m, state.exp_avg)
        if new_v is not None:
            new_v = keep(new_v, state.exp_avg_sq)

        if self.is_low_precision:
            new_params = jax.tree_util.tree_map(
                lambda mp, p: mp.astype(p.dtype), new_masters, params
            )
            master_out = new_masters
        else:
            new_params = new_masters
            master_out = None

        new_state = OptimizerState(
            step=step,
            master_params=master_out,
            exp_avg=new_m,
            exp_avg_sq=new_v,
            grad_scaler=self.grad_scaler.update(state.grad_scaler, found_inf),
        )
        stats = {
            "grad_norm": grad_norm,
            "found_inf": found_inf,
            "loss_scale": scale,
        }
        if layer_stats:
            updates = jax.tree_util.tree_map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_masters, masters,
            )
            stats["layer_stats"] = health.compute_layer_stats(
                masters, unclipped_grads, updates
            )
        return new_params, new_state, stats

    # ------------------------------------------------------------------
    def state_specs(self, param_specs, params, zero1: bool = False,
                    dp_size: int = 1, rules=None):
        """Logical-axis specs for the optimizer state.

        With ``zero1`` (reference DistributedOptimizer,
        distrib_optimizer.py:32-695): master/adam leaves additionally shard
        their first dp-divisible unsharded axis over dp — the GSPMD
        formulation of ZeRO-1 (state memory / dp; XLA inserts the
        reduce-scatter/all-gather pair the reference issues by hand in
        reduce_model_grads/gather_model_params).

        ``rules`` must be the same logical->mesh table the params were
        sharded with (defaults to ``DEFAULT_RULES``): the already-on-dp
        skip below reads it, and a custom table could otherwise map an
        axis onto dp (or off it) differently than the real param layout.
        """

        def shard_dp(spec, leaf):
            if not zero1 or dp_size <= 1:
                return spec
            spec = tuple(spec)
            # a leaf already sharded over dp (MoE 'expert' axis) cannot take
            # a second dp dimension — and needs none: its state memory is
            # already divided by dp
            from megatron_llm_tpu import topology
            from megatron_llm_tpu.parallel.sharding import DEFAULT_RULES

            active = rules if rules is not None else DEFAULT_RULES
            if any(active.get(ax) == topology.DP_AXIS for ax in spec
                   if ax is not None):
                return spec
            for i, (ax, dim) in enumerate(zip(spec, leaf.shape)):
                if ax is None and dim % dp_size == 0:
                    return spec[:i] + ("dp_shard",) + spec[i + 1:]
            return spec

        fp32_specs = jax.tree_util.tree_map(
            shard_dp, param_specs, params,
            is_leaf=lambda s: isinstance(s, tuple),
        )
        return OptimizerState(
            step=None,
            master_params=fp32_specs if self.is_low_precision else None,
            exp_avg=fp32_specs,
            exp_avg_sq=fp32_specs if self.cfg.optimizer == "adam" else None,
            grad_scaler=GradScalerState(scale=None, growth_tracker=None,
                                        hysteresis_tracker=None),
        )

    def shard_zero1(self, opt_state, param_specs, params, dp_size: int, *,
                    verify: bool = True, min_bytes: int = 32 << 10,
                    rules=None):
        """Lay the optimizer state out ZeRO-1 (dp-sharded) on the mesh and
        verify nothing sizeable stayed replicated — the one-call form of
        state_specs + shard + verify used by the driver dryrun and tests.
        Also shards fp32 masters when the optimizer keeps them.  Pass the
        same ``rules`` the params were sharded with (if custom)."""
        from megatron_llm_tpu import topology
        from megatron_llm_tpu.parallel import sharding as sh

        if rules is not None and "dp_shard" not in rules:
            # the synthetic ZeRO-1 axis must map to dp even under custom
            # tables, or the whole state silently stays replicated
            rules = {**rules, "dp_shard": topology.DP_AXIS}

        specs = self.state_specs(param_specs, params, zero1=True,
                                 dp_size=dp_size, rules=rules)
        opt_state = opt_state._replace(
            exp_avg=sh.shard_params(opt_state.exp_avg, specs.exp_avg,
                                    rules=rules),
            exp_avg_sq=(
                sh.shard_params(opt_state.exp_avg_sq, specs.exp_avg_sq,
                                rules=rules)
                if opt_state.exp_avg_sq is not None else None),
            master_params=(
                sh.shard_params(opt_state.master_params,
                                specs.master_params, rules=rules)
                if opt_state.master_params is not None else None),
        )
        if verify and dp_size > 1:
            self.verify_zero1_sharding(opt_state, min_bytes=min_bytes)
        return opt_state

    def verify_zero1_sharding(self, opt_state, *, dp_axis: str = "dp",
                              min_bytes: int = 1 << 20):
        """Assert every master/adam leaf of at least ``min_bytes`` is
        *actually* dp-sharded on the mesh — the ``state_specs`` heuristic
        silently leaves a tensor replicated when no axis is dp-divisible,
        and at 70B that silent fallback is an OOM, not a preference.
        Raises RuntimeError listing every offending leaf."""
        bad = []

        def axes_of(leaf):
            spec = getattr(leaf.sharding, "spec", ())
            names = set()
            for ax in spec or ():
                if isinstance(ax, (tuple, list)):
                    names.update(ax)
                elif ax is not None:
                    names.add(ax)
            return names

        def check(name, tree):
            if tree is None:
                return
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                nbytes = leaf.size * leaf.dtype.itemsize
                if nbytes < min_bytes:
                    continue
                if dp_axis not in axes_of(leaf):
                    bad.append(
                        f"{name}{jax.tree_util.keystr(path)} "
                        f"shape={tuple(leaf.shape)} ({nbytes >> 10} KiB) "
                        f"sharding={leaf.sharding}")

        check("master_params", opt_state.master_params)
        check("exp_avg", opt_state.exp_avg)
        check("exp_avg_sq", opt_state.exp_avg_sq)
        if bad:
            raise RuntimeError(
                "ZeRO-1: optimizer-state leaves not dp-sharded (the "
                "state_specs dp-divisible-axis heuristic fell back to "
                "replication):\n  " + "\n  ".join(bad))


def get_megatron_optimizer(train_cfg: TrainConfig, params_dtype=None):
    """Reference: megatron/optimizer/__init__.py:63."""
    if params_dtype is None:
        params_dtype = (
            jnp.bfloat16 if train_cfg.bf16
            else jnp.float16 if train_cfg.fp16
            else jnp.float32
        )
    return MegatronOptimizer(train_cfg, params_dtype=params_dtype)
