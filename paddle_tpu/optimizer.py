"""Optimizers: program transformation appending per-param update ops.

TPU-native re-design of /root/reference/python/paddle/fluid/optimizer.py
(Optimizer.minimize:586 = backward:442 + apply_gradients:502;
_create_optimization_pass:339; SGD/Momentum/Adagrad/Adam/Adamax/DecayedAdagrad/
Adadelta/RMSProp/Ftrl/Lamb:627-2263; ExponentialMovingAverage:2453;
ModelAverage:2263). Contract kept: `minimize(loss)` appends grad ops (via
append_backward) then one optimizer op per parameter, with accumulator
variables created in both main and startup programs. The reference's
fuse_optimizer_ops pass is unnecessary — all update ops live in one XLA block
and fuse at compile time.
"""
from __future__ import annotations

import numpy as np

from . import observability as obs
from . import unique_name
from .backward import append_backward
from .clip import append_gradient_clip_ops, error_clip_callback
from .framework import Program, Variable, default_main_program, default_startup_program
from .initializer import Constant
from .layer_helper import LayerHelper
from .regularizer import append_regularization_ops

__all__ = [
    "PipelineOptimizer",
    "SGD",
    "SGDOptimizer",
    "Momentum",
    "MomentumOptimizer",
    "DGCMomentumOptimizer",
    "Adagrad",
    "AdagradOptimizer",
    "Adam",
    "AdamOptimizer",
    "Adamax",
    "AdamaxOptimizer",
    "DecayedAdagrad",
    "DecayedAdagradOptimizer",
    "Adadelta",
    "AdadeltaOptimizer",
    "RMSProp",
    "RMSPropOptimizer",
    "Ftrl",
    "FtrlOptimizer",
    "Lamb",
    "LambOptimizer",
    "LarsMomentum",
    "LarsMomentumOptimizer",
    "ModelAverage",
    "LookaheadOptimizer",
    "RecomputeOptimizer",
    "ExponentialMovingAverage",
]


class Optimizer:
    """Base optimizer (reference optimizer.py:60)."""

    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._learning_rate_map: dict[Program, Variable] = {}
        # accumulator name -> {param name -> Variable}
        self._accumulators: dict[str, dict[str, Variable]] = {}
        self.helper: LayerHelper | None = None

    # -- learning rate ------------------------------------------------------
    def _create_global_learning_rate(self):
        program = default_main_program()
        lr = self._learning_rate_map.get(program)
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            # a scheduler already produced an LR variable in this program
            self._learning_rate_map[program] = self._learning_rate
            return
        helper = LayerHelper("learning_rate")
        self._learning_rate_map[program] = helper.create_or_get_global_variable(
            unique_name.generate("learning_rate"),
            [1],
            "float32",
            initializer=Constant(float(self._learning_rate)),
        )

    def _global_learning_rate(self, program=None) -> Variable:
        program = program or default_main_program()
        return self._learning_rate_map[program]

    def _create_param_lr(self, param):
        base_lr = self._global_learning_rate()
        mult = param.optimize_attr.get("learning_rate", 1.0) if param.optimize_attr else 1.0
        if mult == 1.0:
            return base_lr
        from .layers import nn as L

        return L.scale(base_lr, scale=float(mult))

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, dtype="float32", fill_value=0.0, shape=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        helper = LayerHelper(name)
        var = helper.create_or_get_global_variable(
            unique_name.generate(f"{param.name}_{name}"),
            shape if shape is not None else list(param.shape),
            dtype,
            initializer=Constant(fill_value),
        )
        # tag for ZeRO-style sharding (BuildStrategy.sharded_optimizer_states):
        # the compiler may shard these over the dp axis
        var.is_opt_state = True
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- the transformation pipeline ----------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        # the numeric guardrail (resilience/guardrails.py) needs the loss
        # var to build its in-graph health vector; record it on the program
        # (the AMP decorator overwrites this with the UNSCALED loss)
        default_main_program()._guard_loss_name = loss.name
        # graph rewrites that must precede append_backward (fused ops derive
        # their gradients via vjp over the fused lowering) and follow any AMP
        # rewrite (AMP's decorator calls into this backward after its own)
        from .passes import apply_minimize_passes
        from .tuning import on_minimize

        # force the tuning-DB load at minimize() time: a corrupt/missing DB
        # warns HERE (once, attached to the graph build) and every decision
        # below — fusion gating now, conv/attention dispatch at trace —
        # resolves against one consistent snapshot
        on_minimize(default_main_program())
        apply_minimize_passes(default_main_program())
        return append_backward(loss, parameter_list, no_grad_set)

    def apply_gradients(self, params_grads):
        """clip -> regularize -> [health sentinel] -> per-param update ops
        (optimizer.py:502). Under FLAGS_guard_numerics every gradient is
        routed through the in-graph health sentinel AFTER clipping (a NaN
        that a global-norm clip smeared over all grads is still caught), so
        a bad step's update ops see zeros and skip branchlessly."""
        from .resilience import guardrails

        params_grads = sorted(params_grads, key=lambda pg: pg[0].name)
        params_grads = append_gradient_clip_ops(params_grads)
        params_grads = append_regularization_ops(params_grads, self.regularization)
        if guardrails.enabled():
            params_grads = guardrails.append_health_sentinel(params_grads)
        ops = self._create_optimization_pass(params_grads)
        # a program that holds its updates is a train step: a device trace
        # lists its compiled entries as jit_train_step (a name the caller
        # gave the Program stands)
        if default_main_program().name is None:
            default_main_program().name = "train_step"
        # the StepGuard's rewind rung backs the LR off through the scope;
        # record where the LR lives (scheduler LR vars qualify too)
        try:
            default_main_program()._guard_lr_name = (
                self._global_learning_rate().name)
        except (KeyError, AttributeError):
            pass
        return ops

    def _create_optimization_pass(self, params_grads):
        self.helper = LayerHelper(self.__class__.__name__)
        self._create_global_learning_rate()
        self._create_accumulators(
            default_main_program().global_block, [p for p, _ in params_grads]
        )
        ops = []
        for param, grad in params_grads:
            if grad is None or not getattr(param, "trainable", True):
                continue
            ops.append(self._append_optimize_op(default_main_program().global_block, (param, grad)))
        self._finish_update()
        return ops

    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        from . import dygraph as _dy

        if _dy.enabled():
            return self._dygraph_minimize(loss, parameter_list)
        # a training process's Python before any trace: the passes, the
        # grad ops (`setup.backward`), clip, regularizers, update ops
        with obs.span("setup.minimize"):
            params_grads = self.backward(loss, startup_program, parameter_list, no_grad_set)
            optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # -- dygraph (imperative) path ------------------------------------------
    def _dygraph_minimize(self, loss, parameter_list=None):
        """Apply updates to eager parameters after loss.backward() (reference
        dygraph flow: backward() fills VarBase grads, minimize applies).

        parameter_list: VarBase list; defaults to every persistable VarBase
        that participated in the current tape with a gradient.
        """
        import jax.numpy as jnp

        from . import dygraph as _dy

        if parameter_list is None:
            parameter_list = _dy._state.get("last_params") or []
        if not hasattr(self, "_dy_state"):
            self._dy_state = {}
        lr = self._dygraph_lr()
        updated = []
        for p in parameter_list:
            if p._grad is None:
                continue
            g = jnp.asarray(p._grad, p._value.dtype)
            g = self._dygraph_regularize(p._value, g)
            state = self._dy_state.setdefault(p.name, {})
            p._value = self._dygraph_step(p._value, g, lr, state)
            updated.append(p)
        return updated, []

    def _dygraph_regularize(self, value, grad):
        """Weight decay on the eager path (mirror of
        append_regularization_ops in apply_gradients)."""
        from .regularizer import L1DecayRegularizer, L2DecayRegularizer

        reg = self.regularization
        if reg is None:
            return grad
        import jax.numpy as jnp

        if isinstance(reg, L2DecayRegularizer):
            return grad + reg._coeff * value
        if isinstance(reg, L1DecayRegularizer):
            return grad + reg._coeff * jnp.sign(value)
        raise NotImplementedError(
            f"dygraph regularization for {type(reg).__name__}")

    def _dygraph_lr(self):
        lr = self._learning_rate
        if callable(lr):
            lr = lr()
        if isinstance(lr, Variable):
            raise TypeError(
                "dygraph mode needs a float learning rate (schedulers build "
                "static-graph variables)")
        return float(lr)

    def _dygraph_step(self, value, grad, lr, state):
        raise NotImplementedError(
            f"{type(self).__name__} has no dygraph update rule "
            "(SGD/Momentum/Adam support dygraph)")


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "sgd"

    def _dygraph_step(self, value, grad, lr, state):
        return value - lr * grad

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "sgd",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={"ParamOut": [param.name]},
        )


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _dygraph_step(self, value, grad, lr, state):
        import jax.numpy as jnp

        v = state.get("velocity")
        if v is None:
            v = jnp.zeros_like(value)
        v = self._momentum * v + grad
        state["velocity"] = v
        if self._use_nesterov:
            return value - lr * (grad + self._momentum * v)
        return value - lr * v

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "momentum",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Velocity": [velocity.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={"ParamOut": [param.name], "VelocityOut": [velocity.name]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class DGCMomentumOptimizer(MomentumOptimizer):
    """Deep Gradient Compression momentum (reference optimizer.py:805,
    arXiv:1712.01887): each step the `dgc` op sparsifies the gradient to the
    top (1-sparsity) fraction by magnitude with momentum correction and
    error-feedback accumulators, then the regular momentum update consumes
    the sparsified gradient. Under the collective transpiler the allreduce
    rides on the mostly-zero GradOut — the fixed-shape TPU equivalent of the
    reference's sparse communication.

    The warmup rampup (reference __append_dgc_ops' get_sparsity schedule)
    is computed IN-GRAPH from a per-step counter — the same plumbing the LR
    schedules use (layers/learning_rate_scheduler.py): before
    rampup_begin_step sparsity is 0 (every gradient released = plain
    momentum via the error-feedback identity), then it steps through the
    `sparsity` list across rampup_step steps and holds the final value.
    Every dgc op also emits its effective per-step sparsity as a fetchable
    `...dgc_sparsity` var (the oracle tests/test_losses_and_quant.py
    follows).
    """

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum, use_nesterov,
                         regularization, name)
        self.type = "dgc_momentum"
        sp = (list(sparsity) if isinstance(sparsity, (list, tuple))
              else [sparsity])
        self._sparsity_ramp = [float(s) for s in sp]
        self._sparsity = self._sparsity_ramp[-1]
        self._rampup_begin_step = int(rampup_begin_step)
        self._rampup_step = max(1, int(rampup_step))

    def _dgc_step_counter(self):
        """Per-program float32 step counter incremented once per executor
        run, shared by every dgc op in the program (the LR schedulers'
        @LR_DECAY_COUNTER@ pattern with a private name, so a noam_decay
        schedule with a different counter origin can coexist)."""
        helper = LayerHelper("dgc_counter")
        program = default_main_program()
        name = "@DGC_COUNTER@"
        existed = name in program.global_block.vars
        counter = helper.create_or_get_global_variable(
            name, [1], "float32", initializer=Constant(-1.0))
        if not existed:
            # the increment precedes every dgc op in program order, so the
            # first executed step reads 0
            helper.append_op("increment", {"X": [counter]},
                             {"Out": [counter]}, {"step": 1.0})
        return counter

    def _create_accumulators(self, block, parameters):
        # no inherited velocity: momentum lives in dgc_u (the dgc op's
        # momentum correction); the post-compression update is plain sgd
        for p in parameters:
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)

    def _dygraph_step(self, value, grad, lr, state):
        raise NotImplementedError(
            "DGCMomentumOptimizer has no dygraph update rule (falling back "
            "to plain momentum would silently drop the compression)")

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        u = self._get_accumulator("dgc_u", param)
        v = self._get_accumulator("dgc_v", param)
        step = self._dgc_step_counter()
        helper = LayerHelper("dgc")
        sparse_grad = helper.create_variable_for_type_inference(grad.dtype)
        cur_sparsity = helper.create_or_get_global_variable(
            unique_name.generate(f"{param.name}_dgc_sparsity"), [1],
            "float32", initializer=Constant(0.0))
        block.append_op(
            "dgc",
            inputs={"Grad": [grad.name], "U": [u.name], "V": [v.name],
                    "CurrentStep": [step.name]},
            outputs={"GradOut": [sparse_grad.name], "UOut": [u.name],
                     "VOut": [v.name], "Sparsity": [cur_sparsity.name]},
            attrs={"momentum": self._momentum,
                   "sparsity": self._sparsity,
                   "sparsity_ramp": self._sparsity_ramp,
                   "rampup_begin_step": self._rampup_begin_step,
                   "rampup_step": self._rampup_step,
                   "use_nesterov": self._use_nesterov},
        )
        # momentum is already folded into U by the dgc op (momentum
        # correction) — the released gradient applies as plain SGD, the
        # reference dgc_momentum op's post-rampup branch
        return block.append_op(
            "sgd",
            inputs={"Param": [param.name], "Grad": [sparse_grad.name],
                    "LearningRate": [self._create_param_lr(param).name]},
            outputs={"ParamOut": [param.name]},
            attrs={},
        )


class LarsMomentumOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate,
        momentum,
        lars_coeff=0.001,
        lars_weight_decay=0.0005,
        regularization=None,
        name=None,
    ):
        super().__init__(learning_rate, regularization, name)
        self.type = "lars_momentum"
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "lars_momentum",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Velocity": [velocity.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={"ParamOut": [param.name], "VelocityOut": [velocity.name]},
            attrs={
                "mu": self._momentum,
                "lars_coeff": self._lars_coeff,
                "lars_weight_decay": self._lars_weight_decay,
            },
        )


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "adagrad"
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "adagrad",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment": [moment.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={"ParamOut": [param.name], "MomentOut": [moment.name]},
            attrs={"epsilon": self._epsilon},
        )


class AdamOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate=0.001,
        beta1=0.9,
        beta2=0.999,
        epsilon=1e-8,
        regularization=None,
        name=None,
        lazy_mode=False,
    ):
        super().__init__(learning_rate, regularization, name)
        self.type = "adam"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _dygraph_step(self, value, grad, lr, state):
        import jax.numpy as jnp

        m = state.get("m", jnp.zeros_like(value))
        v = state.get("v", jnp.zeros_like(value))
        t = state.get("t", 0) + 1
        m = self._beta1 * m + (1 - self._beta1) * grad
        v = self._beta2 * v + (1 - self._beta2) * grad * grad
        state.update(m=m, v=v, t=t)
        lr_t = lr * (1 - self._beta2 ** t) ** 0.5 / (1 - self._beta1 ** t)
        return value - lr_t * m / (v ** 0.5 + self._epsilon)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1])
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "adam",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment1": [m1.name],
                "Moment2": [m2.name],
                "Beta1Pow": [b1p.name],
                "Beta2Pow": [b2p.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "Moment1Out": [m1.name],
                "Moment2Out": [m2.name],
                "Beta1PowOut": [b1p.name],
                "Beta2PowOut": [b2p.name],
            },
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon},
        )


class AdamaxOptimizer(Optimizer):
    def __init__(
        self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, regularization=None, name=None
    ):
        super().__init__(learning_rate, regularization, name)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "adamax",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment": [self._get_accumulator("moment", param).name],
                "InfNorm": [self._get_accumulator("inf_norm", param).name],
                "Beta1Pow": [self._get_accumulator("beta1_pow_acc", param).name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "MomentOut": [self._get_accumulator("moment", param).name],
                "InfNormOut": [self._get_accumulator("inf_norm", param).name],
            },
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon},
        )

    def _finish_update(self):
        # beta1_pow *= beta1 after each step (reference optimizer.py adamax)
        block = default_main_program().global_block
        for param_name, b1p in self._accumulators.get("beta1_pow_acc", {}).items():
            block.append_op(
                "scale",
                inputs={"X": [b1p.name]},
                outputs={"Out": [b1p.name]},
                attrs={"scale": self._beta1},
            )


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "decayed_adagrad"
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "decayed_adagrad",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment": [moment.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={"ParamOut": [param.name], "MomentOut": [moment.name]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "adadelta"
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        ag = self._get_accumulator("avg_squared_grad", param)
        au = self._get_accumulator("avg_squared_update", param)
        return block.append_op(
            "adadelta",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "AvgSquaredGrad": [ag.name],
                "AvgSquaredUpdate": [au.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "AvgSquaredGradOut": [ag.name],
                "AvgSquaredUpdateOut": [au.name],
            },
            attrs={"epsilon": self._epsilon, "rho": self._rho},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate,
        rho=0.95,
        epsilon=1e-6,
        momentum=0.0,
        centered=False,
        regularization=None,
        name=None,
    ):
        super().__init__(learning_rate, regularization, name)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        mom = self._get_accumulator("momentum", param)
        ms = self._get_accumulator("mean_square", param)
        mg = self._get_accumulator("mean_grad", param)
        return block.append_op(
            "rmsprop",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment": [mom.name],
                "MeanSquare": [ms.name],
                "MeanGrad": [mg.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "MomentOut": [mom.name],
                "MeanSquareOut": [ms.name],
                "MeanGradOut": [mg.name],
            },
            attrs={
                "decay": self._rho,
                "epsilon": self._epsilon,
                "momentum": self._momentum,
                "centered": self._centered,
            },
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, regularization=None, name=None):
        super().__init__(learning_rate, regularization, name)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        sq = self._get_accumulator("squared", param)
        lin = self._get_accumulator("linear", param)
        return block.append_op(
            "ftrl",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "SquaredAccumulator": [sq.name],
                "LinearAccumulator": [lin.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "SquaredAccumOut": [sq.name],
                "LinearAccumOut": [lin.name],
            },
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


class LambOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate=0.001,
        lamb_weight_decay=0.01,
        beta1=0.9,
        beta2=0.999,
        epsilon=1e-6,
        regularization=None,
        name=None,
    ):
        super().__init__(learning_rate, regularization, name)
        self.type = "lamb"
        self._weight_decay = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1])
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "lamb",
            inputs={
                "Param": [param.name],
                "Grad": [grad.name],
                "Moment1": [m1.name],
                "Moment2": [m2.name],
                "Beta1Pow": [b1p.name],
                "Beta2Pow": [b2p.name],
                "LearningRate": [self._create_param_lr(param).name],
            },
            outputs={
                "ParamOut": [param.name],
                "Moment1Out": [m1.name],
                "Moment2Out": [m2.name],
                "Beta1PowOut": [b1p.name],
                "Beta2PowOut": [b2p.name],
            },
            attrs={
                "beta1": self._beta1,
                "beta2": self._beta2,
                "epsilon": self._epsilon,
                "weight_decay": self._weight_decay,
            },
        )


class RecomputeOptimizer:
    """Activation recompute / gradient checkpointing.

    Reference lineage: the fleet DistributedStrategy forward_recompute flag
    and the later fluid RecomputeOptimizer; the TPU-native mechanism here is
    segment-level `jax.checkpoint`. `_set_checkpoints([vars])` names the
    segment boundaries (typically each transformer layer's output); at
    minimize() the forward block is split at those vars, each segment moves
    into a sub-block behind one `recompute` op, and the derived
    `recompute_grad` replays the segment under jax.checkpoint — XLA then
    drops the segment's interior activations after the forward and
    rematerializes them in the backward, trading ~1 extra forward of FLOPs
    for O(#checkpoints) instead of O(#ops) live activation memory.

        opt = RecomputeOptimizer(pt.optimizer.Adam(1e-4))
        opt._set_checkpoints([layer1_out, layer2_out, ...])
        opt.minimize(loss)

    Constraint: RNG-consuming ops (dropout) inside a segment would draw
    different numbers in the replay, so the rewrite rejects them.
    """

    def __init__(self, optimizer):
        self._inner = optimizer
        self._checkpoints = []

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = list(checkpoints)

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        self._rewrite(loss)
        return self._inner.backward(loss, startup_program, parameter_list,
                                    no_grad_set)

    def apply_gradients(self, params_grads):
        return self._inner.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        self._rewrite(loss)
        return self._inner.minimize(loss, startup_program, parameter_list,
                                    no_grad_set)

    # -- the program rewrite -------------------------------------------------
    def _rewrite(self, loss):
        from .ops.registry import get_op_def, has_op

        if not self._checkpoints:
            return
        program = loss.block.program
        block = program.global_block
        ck_names = [getattr(c, "name", c) for c in self._checkpoints]
        ck_set = set(ck_names)
        if getattr(program, "_recompute_done", False):
            return

        # split the forward op list into segments ending at checkpoint defs
        segments, cur = [], []
        matched_any = False
        for op in block.ops:
            cur.append(op)
            if any(n in ck_set for n in op.output_names):
                matched_any = True
                segments.append(cur)
                cur = []
        if cur:
            segments.append(cur)  # tail (loss head) stays inline if short
        if not matched_any:
            raise ValueError(
                "RecomputeOptimizer: no checkpoint variable matched any op "
                "output in this program — the checkpoints likely came from a "
                "different program build (transformer.last_layer_outputs "
                "holds the MOST RECENT build's vars)")
        # suffix read sets in ONE reverse pass (O(total ops), not
        # O(segments x ops)): reads_after[si] = names read in segments > si
        reads_after = [set() for _ in segments]
        acc: set = set()
        for si in range(len(segments) - 1, -1, -1):
            reads_after[si] = set(acc)
            for op in segments[si]:
                for n in op.input_names:
                    if n:
                        acc.add(n)

        new_ops = []
        for si, seg in enumerate(segments[:-1]):
            wrap = [op for op in seg if op.type not in ("feed", "fetch")]
            passthrough = [op for op in seg if op.type in ("feed", "fetch")]
            new_ops.extend(passthrough)
            if len(wrap) < 2:
                new_ops.extend(wrap)
                continue
            for op in wrap:
                if has_op(op.type) and get_op_def(op.type).needs_rng:
                    raise ValueError(
                        f"RecomputeOptimizer: op '{op.type}' consumes RNG "
                        "inside a recompute segment — its replay would draw "
                        "different numbers. Move it out of the segment "
                        "(e.g. dropout=0 under recompute).")
            # names defined inside vs read from outside (insertion-ordered:
            # slot ordering must not depend on PYTHONHASHSEED — program dumps
            # and compile-cache keys have to be reproducible)
            defined: dict = {}
            ext_reads, outs = [], []
            for op in wrap:
                for n in op.input_names:
                    if n and n not in defined and n not in ext_reads:
                        ext_reads.append(n)
                for n in op.output_names:
                    if n:
                        defined[n] = True
            later_reads = reads_after[si]

            def _persistable(n):
                try:
                    return block.var(n).persistable
                except KeyError:
                    return False

            # persistable outputs (batch_norm running stats, counters) must
            # surface even when nothing later reads them — the executor's
            # scope write-back only scans top-level op outputs
            outs = [n for n in defined
                    if n in later_reads or n in ck_set or _persistable(n)]
            # move the segment into a sub-block
            sub = program._create_block()
            for op in wrap:
                sub.ops.append(op)
                op.block = sub
            program._rollback()
            from .framework import Operator

            rec = Operator(
                block, "recompute",
                {"Deps": list(ext_reads)},
                {"Out": list(outs)},
                {"sub_block": sub.idx,
                 "dep_names": list(ext_reads),
                 "out_names": list(outs)},
            )
            new_ops.append(rec)
        new_ops.extend(segments[-1])
        if not any(op.type == "recompute" for op in new_ops):
            raise ValueError(
                "RecomputeOptimizer: checkpoints matched but produced no "
                "recompute segment — each non-tail segment needs >= 2 ops "
                "(is the checkpoint the program's last op, e.g. the loss?)")
        block.ops[:] = new_ops
        program._recompute_done = True
        program._bump_version()


class ModelAverage(Optimizer):
    """Sliding-window parameter averaging (reference optimizer.py:2263).

    Construct AFTER the training optimizer's minimize(): accumulation ops
    append to the main program; `with model_average.apply(exe):` swaps
    parameters for their window averages (restored on exit, or call
    restore()). The reference's three-sum rotation collapses to one
    sum+count with max-window truncation — identical averages over the
    active window.
    """

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, regularization=None, name=None):
        super().__init__(0.0, regularization, name)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self._params = list(default_main_program().all_parameters())
        self.helper = LayerHelper(self.__class__.__name__)
        total = self.helper.create_or_get_global_variable(
            unique_name.generate("ma_total_updates"), [1], "float32",
            initializer=Constant(0.0))
        default_main_program().global_block.append_op(
            "increment", {"X": [total.name]}, {"Out": [total.name]},
            {"step": 1.0})
        for p in self._params:
            s = self._add_accumulator("ma_sum", p, dtype=p.dtype)
            c = self._add_accumulator("ma_cnt", p, shape=[1])
            default_main_program().global_block.append_op(
                "model_average_accum",
                inputs={"Param": [p.name], "Sum": [s.name], "Cnt": [c.name],
                        "TotalUpdates": [total.name]},
                outputs={"SumOut": [s.name], "CntOut": [c.name]},
                attrs={"max_average_window": float(max_average_window),
                       "min_average_window": float(min_average_window),
                       "average_window_rate": float(average_window_rate)},
            )

    def _swap(self, executor, to_average: bool):
        import jax.numpy as jnp

        from .executor import global_scope

        scope = global_scope()
        for p in self._params:
            if to_average:
                s = np.asarray(scope.find_var(
                    self._accumulators["ma_sum"][p.name].name))
                c = float(np.asarray(scope.find_var(
                    self._accumulators["ma_cnt"][p.name].name)).reshape(-1)[0])
                self._backup[p.name] = scope.find_var(p.name)
                if c > 0:
                    scope.set_var(p.name, jnp.asarray(s / c, s.dtype))
            else:
                scope.set_var(p.name, self._backup[p.name])

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def guard():
            self._backup = {}
            self._swap(executor, True)
            try:
                yield
            finally:
                if need_restore:
                    self._swap(executor, False)

        return guard()

    def restore(self, executor=None):
        self._swap(executor, False)


class LookaheadOptimizer:
    """Lookahead wrapper (reference optimizer.py:2976, arXiv:1907.08610):
    the inner optimizer updates fast weights every step; every k steps the
    slow weights catch up and overwrite the fast ones."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        if inner_optimizer is None:
            raise ValueError("inner optimizer cannot be None")
        assert 0.0 <= alpha <= 1.0, "alpha should be in [0.0, 1.0]"
        assert isinstance(k, int) and k > 0, "k should be a positive integer"
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        ops, pgs = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        helper = LayerHelper("lookahead")
        block = default_main_program().global_block
        step = helper.create_or_get_global_variable(
            unique_name.generate("lookahead_step"), [1], "float32",
            initializer=Constant(0.0))
        # increment ONCE, then every parameter's sync op reads the same tick
        block.append_op("increment", {"X": [step.name]},
                        {"Out": [step.name]}, {"step": 1.0})
        for p, g in pgs:
            if g is None:
                continue
            slow = helper.create_or_get_global_variable(
                unique_name.generate(p.name + "_slow"), list(p.shape),
                p.dtype, initializer=None)
            # slow starts equal to fast: copy in the startup program
            default_startup_program().global_block.append_op(
                "assign", {"X": [p.name]}, {"Out": [slow.name]}, {})
            block.append_op(
                "lookahead",
                inputs={"Param": [p.name], "SlowParam": [slow.name],
                        "Step": [step.name]},
                outputs={"ParamOut": [p.name], "SlowOut": [slow.name]},
                attrs={"alpha": self.alpha, "k": float(self.k)},
            )
        return ops, pgs


class ExponentialMovingAverage:
    """EMA of parameters (reference optimizer.py:2453).

    `update()` appends shadow-update ops (+ a step counter) to the main
    program; `apply(executor)` is a context manager that swaps bias-corrected
    shadow values into the params in the scope for eval and restores them on
    exit (the reference does the same via temp programs)."""

    def __init__(self, decay=0.999, name=None):
        self._decay = decay
        self._name = name or "ema"
        self._shadows: dict[str, Variable] = {}
        self._step_var: Variable | None = None

    def update(self):
        block = default_main_program().global_block
        helper = LayerHelper(self._name)
        self._step_var = helper.create_or_get_global_variable(
            f"{self._name}.step", [1], "float32", initializer=Constant(0.0)
        )
        block.append_op(
            "increment",
            inputs={"X": [self._step_var.name]},
            outputs={"Out": [self._step_var.name]},
            attrs={"step": 1.0},
        )
        for param in default_main_program().all_parameters():
            shadow = helper.create_or_get_global_variable(
                f"{param.name}.{self._name}", list(param.shape), param.dtype.value
            )
            self._shadows[param.name] = shadow
            # shadow = decay*shadow + (1-decay)*param, as ops
            tmp = helper.create_variable_for_type_inference(param.dtype)
            block.append_op(
                "scale",
                inputs={"X": [shadow.name]},
                outputs={"Out": [tmp.name]},
                attrs={"scale": self._decay},
            )
            tmp2 = helper.create_variable_for_type_inference(param.dtype)
            block.append_op(
                "scale",
                inputs={"X": [param.name]},
                outputs={"Out": [tmp2.name]},
                attrs={"scale": 1.0 - self._decay},
            )
            block.append_op(
                "sum", inputs={"X": [tmp.name, tmp2.name]}, outputs={"Out": [shadow.name]}
            )

    def apply(self, executor=None, need_restore=True):
        """Context manager: params <- shadow / (1 - decay^step) in the scope."""
        import contextlib

        from .executor import global_scope

        @contextlib.contextmanager
        def _ctx():
            scope = global_scope()
            step = float(np.asarray(scope.find_var(self._step_var.name))[0]) if self._step_var else 0.0
            correction = 1.0 - self._decay ** max(step, 1.0)
            backup = {}
            for pname, shadow in self._shadows.items():
                backup[pname] = scope.find_var(pname)
                sval = np.asarray(scope.find_var(shadow.name))
                scope.set_var(pname, (sval / correction).astype(sval.dtype))
            try:
                yield
            finally:
                if need_restore:
                    for pname, val in backup.items():
                        scope.set_var(pname, val)

        return _ctx()

    def restore(self, executor=None):
        pass  # restoration handled by the apply() context manager


class PipelineOptimizer:
    """Pipeline-parallel training (reference optimizer.py:2683).

    Wraps an inner optimizer; `minimize` cuts the forward program at
    `cut_list` variables into stages and attaches a GPipe microbatch plan
    (parallel/pipeline.py). `Executor.run` on the program then executes the
    full schedule: per-microbatch forward, rematerialized backward with
    gradient accumulation, one inner-optimizer step.

    `place_list` maps one device per stage (reference SectionConfig places,
    trainer_desc.proto:74): stage parameters/optimizer state live on that
    device, boundary tensors transfer device-to-device, and the microbatch
    loop runs in clock-cycle order so stages overlap (SectionWorker
    concurrency via XLA async dispatch). Entries: jax.Device, int ordinal,
    or TPUPlace/CUDAPlace-style objects with `device_id`.
    `concurrency_list`/`queue_size`/`start_cpu_core_id` are accepted for
    reference API parity; XLA async dispatch replaces section threads and
    scope queues.
    """

    def __init__(self, optimizer, cut_list=None, place_list=None,
                 concurrency_list=None, queue_size=30, sync_steps=1,
                 start_cpu_core_id=0, num_microbatches=4, schedule=None):
        self._inner_opt = optimizer
        self._cut_list = cut_list or []
        self._place_list = place_list
        self._num_microbatches = num_microbatches
        # "1f1b" (default via FLAGS_pipeline_schedule) or "gpipe"; both are
        # numerically identical — 1f1b bounds the boundary stash at ~n_stages
        # microbatches where gpipe's grows with num_microbatches
        self._schedule = schedule

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .parallel.pipeline import build_pipeline_plan

        if isinstance(self._inner_opt._learning_rate, Variable):
            raise NotImplementedError(
                "PipelineOptimizer does not support LR-scheduler Variables "
                "yet: the scheduler ops live in the sliced forward program "
                "and would never run for the stage update programs. Use a "
                "float learning rate.")
        cuts = []
        for group in self._cut_list:
            cuts.extend(group if isinstance(group, (list, tuple)) else [group])
        program = loss.block.program
        program._pipeline = build_pipeline_plan(
            program, loss, cuts, self._inner_opt, self._num_microbatches,
            startup_program, devices=self._place_list,
            schedule=self._schedule)
        return [], []


# short aliases matching the reference's public names (optimizer.py:2988+)
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
