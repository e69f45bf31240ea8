"""Optimizers: emit backward + optimizer ops into the program.

Reference counterpart: python/paddle/fluid/optimizer.py (5,248 LoC; Optimizer
base at the top, `minimize` = append_backward + apply_gradients). Same
structure: each optimizer creates accumulator vars (moments etc.) as
persistable parameters-of-the-optimizer and appends one device-side update op
per parameter (ops/optimizer_ops.py). The whole train step — forward, backward,
and all update ops — lowers to ONE XLA computation, so there is no per-op
dispatch overhead at all (the reference runs each optimizer op separately).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .framework import unique_name
from .framework.backward import append_backward
from .framework.program import (OpRole, Parameter, Variable,
                                default_main_program, default_startup_program)
from .framework.dtype import dtype_name
from .layer_helper import LayerHelper
from . import initializer as init_mod
from . import layers
from .observability.trace import RecordEvent

__all__ = [
    "Optimizer", "SGD", "SGDOptimizer", "Momentum", "MomentumOptimizer",
    "Adam", "AdamOptimizer", "AdamW", "Adagrad", "AdagradOptimizer",
    "Adamax", "AdamaxOptimizer", "RMSProp", "RMSPropOptimizer",
    "Lamb", "LambOptimizer", "LarsMomentum", "LarsMomentumOptimizer",
    "ExponentialMovingAverage", "ModelAverage", "Adadelta",
    "AdadeltaOptimizer", "Ftrl", "FtrlOptimizer", "Dpsgd", "DpsgdOptimizer",
    "DecayedAdagrad", "DecayedAdagradOptimizer", "DGCMomentumOptimizer",
    "LookaheadOptimizer", "RecomputeOptimizer", "GradientMergeOptimizer",
    "PipelineOptimizer",
    "lr",
]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameter_list=None,
                 regularization=None, grad_clip=None, name=None,
                 parameters=None, weight_decay=None):
        self._learning_rate = learning_rate
        # paddle 2.0 spelling: parameters= / weight_decay=
        self._parameter_list = (parameter_list if parameter_list is not None
                                else parameters)
        if regularization is None and weight_decay:
            from .regularizer import L2Decay
            regularization = (weight_decay if not isinstance(
                weight_decay, (int, float)) else L2Decay(weight_decay))
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name or unique_name.generate(type(self).__name__)
        self._accumulators: Dict[str, Dict[str, Variable]] = {}
        self._lr_var = None
        self.helper = LayerHelper(type(self).__name__)
        self.type = "sgd"

    # -- learning rate ------------------------------------------------------
    def _create_lr_var(self):
        if self._lr_var is not None:
            return self._lr_var
        from .framework.program import in_dygraph_mode
        from .lr import LRScheduler
        lr = self._learning_rate
        if isinstance(lr, Variable):
            self._lr_var = lr
        elif isinstance(lr, LRScheduler):
            # static mode: persistable LR var the scheduler refreshes in the
            # global scope on step() — device state, no recompiles
            name = unique_name.generate("learning_rate")
            self._lr_var = layers.create_global_var(
                [1], float(lr()), "float32", persistable=True, name=name)
            lr._bind_static_var(name)
        elif callable(lr):
            self._lr_var = lr()
        else:
            name = unique_name.generate("learning_rate")
            self._lr_var = layers.create_global_var(
                [1], float(lr), "float32", persistable=True, name=name)
        return self._lr_var

    @property
    def learning_rate_var(self):
        return self._create_lr_var()

    def set_lr(self, value):
        from .framework.scope import global_scope
        import jax.numpy as jnp
        self._create_lr_var()
        global_scope().set(self._lr_var.name,
                           jnp.asarray([value], jnp.float32))

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None,
                         dtype=None):
        if name in self._accumulators and \
                param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        var = layers.create_global_var(
            shape or list(param.shape), fill_value,
            dtype or dtype_name(param.dtype), persistable=True,
            name=unique_name.generate(f"{param.name}_{name}"))
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- the hooks subclasses implement -------------------------------------
    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _create_accumulators(self, block, parameters):
        pass

    def _finalize_optimize_ops(self, block):
        """Ops appended ONCE after the per-parameter update ops (e.g. the
        shared beta-pow advance, reference optimizer.py _finish_update).
        Returns the list of appended Operators so wrappers (gradient merge)
        can gate their state writes like any other optimizer op."""
        return []

    # -- public API ---------------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        return append_backward(loss, parameter_list or self._parameter_list,
                               no_grad_set)

    def apply_gradients(self, params_grads):
        block = default_main_program().global_block()
        # grad clip (reference fluid/clip.py applied here)
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        # regularization (reference regularizer.py: appended to grads)
        params_grads = self._append_regularization(params_grads)
        self._create_accumulators(block,
                                  [p for p, _ in params_grads])
        self._create_lr_var()
        for pg in params_grads:
            op = self._append_optimize_op(block, pg)
            if op is not None:
                op.attrs["op_role"] = OpRole.Optimize
                # the update's device work under one name in the compiled
                # step and in a profiler capture (program.name_scope)
                op.attrs.setdefault("name_scope", f"optimizer.{self.type}")
        for op in self._finalize_optimize_ops(block):
            op.attrs["op_role"] = OpRole.Optimize
        return []

    def _append_regularization(self, params_grads):
        out = []
        for p, g in params_grads:
            reg = getattr(p, "regularizer", None) or self.regularization
            # SelectedRows grads skip regularization, like the reference
            # (regularizer.py warns and skips sparse grads)
            if reg is not None and not getattr(g, "_is_selected_rows", False):
                g = reg._append(p, g)
            out.append((p, g))
        return out

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        with RecordEvent("optimizer.minimize"):
            params_grads = self.backward(loss, startup_program,
                                         parameter_list, no_grad_set)
            self.apply_gradients(params_grads)
        return [], params_grads

    # dygraph API
    def step(self):
        from .dygraph.tracer import current_tracer
        current_tracer().optimizer_step(self)

    def clear_grad(self):
        from .dygraph.tracer import current_tracer
        current_tracer().clear_grads(self._parameter_list)

    def state_dict(self):
        from .framework.scope import global_scope
        sd = {}
        for accs in self._accumulators.values():   # static-graph accumulators
            for v in accs.values():
                sd[v.name] = np.asarray(global_scope().find(v.name))
        for pname, accs in getattr(self, "_eager_acc", {}).items():
            for aname, val in accs.items():        # dygraph accumulators
                sd[f"{pname}/{aname}"] = np.asarray(val)
        return sd

    def set_state_dict(self, sd):
        from .framework.scope import global_scope
        import jax.numpy as jnp
        static_names = {v.name for accs in self._accumulators.values()
                        for v in accs.values()}
        for key, val in sd.items():
            if "/" in key and key not in static_names:
                pname, aname = key.rsplit("/", 1)
                if not hasattr(self, "_eager_acc"):
                    self._eager_acc = {}
                self._eager_acc.setdefault(pname, {})[aname] = jnp.asarray(val)
            else:
                global_scope().set(key, jnp.asarray(val))


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "sgd"

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "sgd",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p]},
            attrs={"op_role": OpRole.Optimize})


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, use_nesterov=False,
                 **kw):
        super().__init__(learning_rate, **kw)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p], "VelocityOut": [v]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov,
                   "op_role": OpRole.Optimize})


class LarsMomentumOptimizer(Optimizer):
    """Reference optimizer.py:1605 LarsMomentumOptimizer."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, epsilon=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "lars_momentum"
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": [p], "Grad": [g], "Velocity": [v],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p], "VelocityOut": [v]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay,
                   "epsilon": self._epsilon, "op_role": OpRole.Optimize})


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adam"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    # The beta-pow accumulators are SHARED across parameters: every
    # per-param pow holds the identical value beta^t at every step, and one
    # [1]-buffer per param per beta costs an in-place-aliasing copy per step
    # in the compiled program — 2N copy ops that dominated the copy census
    # of the BERT train step (docs/perf_notes.md "Copy census"). The pair
    # advances ONCE per step via _finalize_optimize_ops, after every adam op
    # has read the old value (reference AdamOptimizer._finish_update appends
    # its pow scales after the update ops for the same reason).
    def _shared_pow_accumulator(self, idx, beta):
        accs = self._accumulators.setdefault(f"beta{idx}_pow_acc", {})
        if "@SHARED@" not in accs:
            var = layers.create_global_var(
                [1], beta, "float32", persistable=True,
                name=unique_name.generate(f"{self.type}_beta{idx}_pow_acc"))
            accs["@SHARED@"] = var
        return accs["@SHARED@"]

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
        for idx, beta in ((1, self._beta1), (2, self._beta2)):
            var = self._shared_pow_accumulator(idx, beta)
            # record the EXACT legacy-checkpoint names this shared var
            # supersedes (checkpoints written before the sharing carried
            # one <param>_beta{idx}_pow_acc_<n> per param) so the
            # executor's adoption hook (_ensure_shared_beta_pows, from
            # Executor._resolve_call) can do
            # O(1) lookups against a closed list — never a scope scan,
            # and never another live program's shared pow var
            prog = var.block.program
            reg = dict(getattr(prog, "_shared_beta_pows", {}))
            names = set(reg.get(var.name, ()))
            names.update(f"{p.name}_beta{idx}_pow_acc_0"
                         for p in parameters)
            reg[var.name] = sorted(names)
            prog._shared_beta_pows = reg

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._shared_pow_accumulator(1, self._beta1)
        b2p = self._shared_pow_accumulator(2, self._beta2)
        # Beta{1,2}PowOut deliberately absent from the outputs: the shared
        # advance is one scale op appended by _finalize_optimize_ops
        return block.append_op(
            self.type,
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._lr_var],
                    "Moment1": [m1], "Moment2": [m2],
                    "Beta1Pow": [b1p], "Beta2Pow": [b2p]},
            outputs={"ParamOut": [p], "Moment1Out": [m1], "Moment2Out": [m2]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "op_role": OpRole.Optimize,
                   **self._extra_attrs()})

    def _finalize_optimize_ops(self, block):
        ops = []
        for idx, beta in ((1, self._beta1), (2, self._beta2)):
            pow_var = self._shared_pow_accumulator(idx, beta)
            already = any(
                op.attrs.get("__adam_pow_advance__") == pow_var.name
                for op in block.ops)
            if already:   # a second apply_gradients on the same block must
                continue  # not advance the pows twice per step
            ops.append(block.append_op(
                "scale", inputs={"X": [pow_var]},
                outputs={"Out": [pow_var]},
                attrs={"scale": beta, "op_role": OpRole.Optimize,
                       "__adam_pow_advance__": pow_var.name}))
        return ops

    def _extra_attrs(self):
        return {}


class AdamW(AdamOptimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, weight_decay=0.01, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.type = "adamw"
        self._coeff = weight_decay

    def _extra_attrs(self):
        return {"coeff": self._coeff, "with_decay": True}


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adagrad"
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [m],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p], "MomentOut": [m]},
            attrs={"epsilon": self._epsilon, "op_role": OpRole.Optimize})


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=[1])

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "adamax",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._lr_var],
                    "Moment": [self._get_accumulator("moment", p)],
                    "InfNorm": [self._get_accumulator("inf_norm", p)],
                    "Beta1Pow": [self._get_accumulator("beta1_pow_acc", p)]},
            outputs={"ParamOut": [p],
                     "MomentOut": [self._get_accumulator("moment", p)],
                     "InfNormOut": [self._get_accumulator("inf_norm", p)]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon, "op_role": OpRole.Optimize})


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)
            self._add_accumulator("momentum", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "rmsprop",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._lr_var],
                    "MeanSquare": [self._get_accumulator("mean_square", p)],
                    "MeanGrad": [self._get_accumulator("mean_grad", p)],
                    "Moment": [self._get_accumulator("momentum", p)]},
            outputs={"ParamOut": [p],
                     "MeanSquareOut": [self._get_accumulator("mean_square", p)],
                     "MeanGradOut": [self._get_accumulator("mean_grad", p)],
                     "MomentOut": [self._get_accumulator("momentum", p)]},
            attrs={"decay": self._rho, "epsilon": self._epsilon,
                   "momentum": self._momentum, "centered": self._centered,
                   "op_role": OpRole.Optimize})


class LambOptimizer(AdamOptimizer):
    """Reference optimizer.py:2962 LambOptimizer."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.type = "lamb"
        self._weight_decay = lamb_weight_decay

    def _extra_attrs(self):
        return {"weight_decay": self._weight_decay}


class ExponentialMovingAverage:
    """Reference optimizer.py:3443: maintains shadow EMA params.

    TPU-native: the EMA update for all params is a handful of fused multiply-
    adds inside the same XLA program as the train step.
    """

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or "ema"
        self._shadows = {}
        self._backups = {}

    def update(self):
        program = default_main_program()
        block = program.global_block()
        for p in program.all_parameters():
            if not p.trainable:
                continue
            shadow = self._shadows.get(p.name)
            if shadow is None:
                shadow = layers.create_global_var(
                    list(p.shape), 0.0, dtype_name(p.dtype), persistable=True,
                    name=unique_name.generate(f"{p.name}_{self._name}"))
                # start shadow at the param value
                init_block = default_startup_program().global_block()
                if p.name in init_block.vars or True:
                    pass
                self._shadows[p.name] = shadow
            # shadow = decay * shadow + (1-decay) * param
            scaled = layers.scale(shadow, scale=self._decay)
            contrib = layers.scale(p, scale=1.0 - self._decay)
            layers.sums([scaled, contrib], out=shadow)
            for op in block.ops[-3:]:
                op.attrs["op_role"] = OpRole.Optimize

    def apply(self, executor=None, need_restore=True):
        from .framework.scope import global_scope
        scope = global_scope()
        for pname, shadow in self._shadows.items():
            self._backups[pname] = scope.find(pname)
            scope.set(pname, scope.find(shadow.name))

    def restore(self, executor=None):
        from .framework.scope import global_scope
        scope = global_scope()
        for pname, val in self._backups.items():
            scope.set(pname, val)
        self._backups.clear()


class ModelAverage(ExponentialMovingAverage):
    """Reference optimizer.py:3134 — approximated as high-decay EMA (documented
    divergence: the reference keeps windowed sums)."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, **kw):
        super().__init__(decay=0.999)


class AdadeltaOptimizer(Optimizer):
    """Reference optimizer.py AdadeltaOptimizer (operators adadelta_op)."""

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adadelta"
        self._rho, self._epsilon = rho, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        asg = self._get_accumulator("avg_squared_grad", p)
        asu = self._get_accumulator("avg_squared_update", p)
        return block.append_op(
            "adadelta",
            inputs={"Param": [p], "Grad": [g], "AvgSquaredGrad": [asg],
                    "AvgSquaredUpdate": [asu]},
            outputs={"ParamOut": [p], "AvgSquaredGradOut": [asg],
                     "AvgSquaredUpdateOut": [asu]},
            attrs={"rho": self._rho, "epsilon": self._epsilon,
                   "op_role": OpRole.Optimize})


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "decayed_adagrad"
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [p], "Grad": [g], "Moment": [m],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p], "MomentOut": [m]},
            attrs={"decay": self._decay, "epsilon": self._epsilon,
                   "op_role": OpRole.Optimize})


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "ftrl",
            inputs={"Param": [p], "Grad": [g],
                    "SquaredAccumulator": [self._get_accumulator("squared", p)],
                    "LinearAccumulator": [self._get_accumulator("linear", p)],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p],
                     "SquaredAccumOut": [self._get_accumulator("squared", p)],
                     "LinearAccumOut": [self._get_accumulator("linear", p)]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power,
                   "op_role": OpRole.Optimize})


class DpsgdOptimizer(Optimizer):
    """Differentially-private SGD (reference optimizer.py DpsgdOptimizer)."""

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "dpsgd"
        self._clip, self._batch_size, self._sigma = clip, batch_size, sigma

    def _append_optimize_op(self, block, pg):
        p, g = pg
        return block.append_op(
            "dpsgd",
            inputs={"Param": [p], "Grad": [g],
                    "LearningRate": [self._lr_var]},
            outputs={"ParamOut": [p]},
            attrs={"clip": self._clip, "batch_size": self._batch_size,
                   "sigma": self._sigma, "op_role": OpRole.Optimize})


class DGCMomentumOptimizer(MomentumOptimizer):
    """Reference optimizer.py:1185 + operators/dgc_op.h. Full DGC semantics:
    per-param U (momentum-corrected accumulation) and V (residual) state, a
    rampup sparsity schedule, sampled-top-k threshold selection, momentum
    factor masking, and the momentum→SGD switch at rampup_begin_step
    (dgc_momentum_op.h:44). Documented TPU divergence: the sparsified
    gradient still crosses chips as a DENSE XLA allreduce over ICI (GSPMD
    owns the collective; ICI makes wire compression pointless) — what DGC
    changes here is the UPDATE RULE, which is the part that affects
    convergence."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None, **kw):
        super().__init__(learning_rate, momentum, use_nesterov, **kw)
        self.type = "dgc_momentum"
        self._rampup_begin_step = float(rampup_begin_step)
        self._rampup_step = float(rampup_step)
        self._sparsity = [float(s) for s in sparsity]
        self._local_grad_clip_norm = local_grad_clip_norm
        self._counter_var = None

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)
        if self._counter_var is None:
            self._counter_var = layers.create_global_var(
                [1], 0.0, "float32", persistable=True,
                name=unique_name.generate("dgc_counter"))

    def _append_optimize_op(self, block, pg):
        p, g = pg
        u = self._get_accumulator("dgc_u", p)
        v = self._get_accumulator("dgc_v", p)
        vel = self._get_accumulator("velocity", p)
        step = self._counter_var
        if self._local_grad_clip_norm is not None:
            clipped = block.create_var(
                name=unique_name.generate(f"{p.name}_dgc_clip"),
                shape=p.shape, dtype=p.dtype)
            block.append_op(
                "dgc_clip_by_norm",
                inputs={"X": [g], "current_step": [step]},
                outputs={"Out": [clipped]},
                attrs={"max_norm": float(self._local_grad_clip_norm),
                       "rampup_begin_step": self._rampup_begin_step,
                       "op_role": OpRole.Optimize})
            g = clipped
        encoded = block.create_var(
            name=unique_name.generate(f"{p.name}_dgc_encoded"),
            shape=p.shape, dtype=p.dtype)
        block.append_op(
            "dgc",
            inputs={"U": [u], "V": [v], "Grad": [g],
                    "current_step": [step]},
            outputs={"UOut": [u], "VOut": [v], "EncodeGrad": [encoded]},
            attrs={"m": self._momentum,
                   "rampup_begin_step": self._rampup_begin_step,
                   "rampup_step": self._rampup_step,
                   "sparsity": self._sparsity,
                   "op_role": OpRole.Optimize})
        return block.append_op(
            "dgc_momentum",
            inputs={"Param": [p], "Grad": [encoded], "Velocity": [vel],
                    "LearningRate": [self._lr_var],
                    "current_step": [step]},
            outputs={"ParamOut": [p], "VelocityOut": [vel]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov,
                   "rampup_begin_step": self._rampup_begin_step,
                   "op_role": OpRole.Optimize})

    def apply_gradients(self, params_grads):
        out = super().apply_gradients(params_grads)
        block = default_main_program().global_block()
        block.append_op("increment",
                        inputs={"X": [self._counter_var]},
                        outputs={"Out": [self._counter_var]},
                        attrs={"step": 1.0, "op_role": OpRole.Optimize})
        return out


class LookaheadOptimizer:
    """Reference optimizer.py:4853: slow/fast weights; every k steps the slow
    copy moves toward the fast weights and the fast weights reset to it.
    The periodic sync runs as a host-side scope update (cheap: k is small)."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k
        self._step = 0
        self._slow = {}
        self._params = None

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        res = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        self._params = [p for p, _ in res[1]]
        return res

    def sync(self):
        """Call once per executor step (reference inserts the sync ops into
        the program; host-side here keeps the jitted step donation-friendly)."""
        from .framework.scope import global_scope
        if self._params is None:
            raise RuntimeError(
                "LookaheadOptimizer.sync() before minimize(): the wrapper "
                "must own the minimize call to know the parameter set")
        scope = global_scope()
        if not self._slow:
            # seed slow weights at the window start (pre-update values)
            for p in self._params:
                self._slow[p.name] = np.asarray(scope.find(p.name))
        self._step += 1
        if self._step % self.k:
            return
        for p in self._params:
            # host numpy copies: scope arrays get DONATED to the next jitted
            # step, so cached device references would be invalidated
            fast = np.asarray(scope.find(p.name))
            slow = self._slow.get(p.name)
            if slow is None:
                slow = fast
            slow = slow + self.alpha * (fast - slow)
            self._slow[p.name] = slow
            scope.set(p.name, slow)


class PipelineOptimizer:
    """Reference optimizer.py:3695 PipelineOptimizer + SectionWorker
    (framework/section_worker.cc). TPU-native GPipe: minimize marks the
    program with the microbatch count; the Executor then runs LR-sched ops
    once, scans the fwd+bwd section over microbatch slices of every feed
    accumulating grads, and applies the optimizer ops once — one fused XLA
    program (see executor._run_block_microbatched). `fluid.device_guard`
    stage annotations ride along as op metadata for stage-aware sharding."""

    def __init__(self, optimizer, num_microbatches=1, start_cpu_core_id=0):
        self.inner_optimizer = optimizer
        self.num_microbatches = int(num_microbatches)

    def __getattr__(self, item):
        return getattr(self.inner_optimizer, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        res = self.inner_optimizer.minimize(loss, startup_program,
                                            parameter_list, no_grad_set)
        program = loss.block.program
        program._microbatch_k = self.num_microbatches
        program.bump_version()
        return res


def RecomputeOptimizer(inner_optimizer, checkpoints=None):
    """Reference optimizer.py:4547 — activation checkpointing. TPU-native via
    jax.remat segments (parallel/transforms.apply_recompute)."""
    from .parallel.transforms import RecomputeWrapper
    return RecomputeWrapper(inner_optimizer, checkpoints or [])


def GradientMergeOptimizer(inner_optimizer, k_steps=1, avg=True):
    """Reference optimizer.py:5025 — micro-batch gradient accumulation."""
    from .parallel.transforms import GradientMergeWrapper
    return GradientMergeWrapper(inner_optimizer, k_steps, avg=avg)


# 2.0-style aliases
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
Adagrad = AdagradOptimizer
Adamax = AdamaxOptimizer
RMSProp = RMSPropOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
Adadelta = AdadeltaOptimizer
Ftrl = FtrlOptimizer
Dpsgd = DpsgdOptimizer
DecayedAdagrad = DecayedAdagradOptimizer

from . import lr  # noqa: E402  (paddle.optimizer.lr.* scheduler classes)
