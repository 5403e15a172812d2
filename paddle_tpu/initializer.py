"""Initializers: append init ops to the startup program.

Reference: /root/reference/python/paddle/fluid/initializer.py (Constant:59,
Uniform:133, Normal:199, Xavier:327, MSRA:443, TruncatedNormal). Same design:
an Initializer is a callable that appends one op writing the parameter in the
*startup* program; the TPU executor runs that block once to materialize
params in the Scope.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = [
    "Constant",
    "Uniform",
    "Normal",
    "TruncatedNormal",
    "Xavier",
    "MSRA",
    "NumpyArrayInitializer",
]


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError

    def _dygraph_sample(self, key, shape, dtype, fan_in=None, fan_out=None):
        """Eager sampling for dygraph create_parameter (same distribution the
        static op path produces, drawn from the dygraph guard's PRNG)."""
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, var, block):
        block.append_op(
            "fill_constant",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "dtype": var.dtype.value, "value": self.value},
        )

    def _dygraph_sample(self, key, shape, dtype, fan_in=None, fan_out=None):
        return np.full(shape, self.value, dtype)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op(
            "uniform_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype.value,
                "min": self.low,
                "max": self.high,
                "seed": self.seed,
            },
        )

    def _dygraph_sample(self, key, shape, dtype, fan_in=None, fan_out=None):
        import jax

        return jax.random.uniform(key, shape, dtype, self.low, self.high)


class Normal(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op(
            "gaussian_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype.value,
                "mean": self.loc,
                "std": self.scale,
                "seed": self.seed,
            },
        )

    def _dygraph_sample(self, key, shape, dtype, fan_in=None, fan_out=None):
        import jax

        return jax.random.normal(key, shape, dtype) * self.scale + self.loc


class StackedNormal(Normal):
    """Normal for a stack of layers `[L, ...]` too large to draw at once
    (a served mixture-of-experts' expert weights): the startup op draws one
    leading index at a time."""

    def __call__(self, var, block):
        block.append_op(
            "stacked_gaussian_random",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "dtype": var.dtype.value,
                   "mean": self.loc, "std": self.scale, "seed": self.seed},
        )


class BlockedNormal(Normal):
    """Normal for a matrix `[..., R, W]` too large to draw at once and,
    with `columns` ((width, scale) pairs that add up to W), of another
    scale a block of columns: the startup op draws `block_rows` rows at a
    time (all R by default) and multiplies the columns by their scales
    (`scale` where there are no `columns`)."""

    def __init__(self, scale=1.0, columns=None, block_rows=None, seed=0):
        super().__init__(0.0, scale, seed)
        self.columns = columns
        self.block_rows = block_rows

    def __call__(self, var, block):
        widths, scales = zip(*(self.columns
                               or [(var.shape[-1], self.scale)]))
        block.append_op(
            "blocked_gaussian_random",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "dtype": var.dtype.value,
                   "col_widths": [int(w) for w in widths],
                   "col_scales": [float(v) for v in scales],
                   "block_rows": int(self.block_rows or var.shape[-2]),
                   "seed": self.seed},
        )


class TruncatedNormal(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op(
            "truncated_gaussian_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype.value,
                "mean": self.loc,
                "std": self.scale,
                "seed": self.seed,
            },
        )


def _fan_in_out(var):
    shape = var.shape
    if len(shape) < 2:
        return (shape[0] if shape else 1), (shape[0] if shape else 1)
    receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    return shape[0] * receptive, shape[1] * receptive


class Xavier(Initializer):
    """Glorot init (reference initializer.py:327)."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self.uniform, self.fan_in, self.fan_out, self.seed = uniform, fan_in, fan_out, seed

    def __call__(self, var, block):
        f_in, f_out = _fan_in_out(var)
        f_in = self.fan_in if self.fan_in is not None else f_in
        f_out = self.fan_out if self.fan_out is not None else f_out
        if self.uniform:
            limit = math.sqrt(6.0 / (f_in + f_out))
            Uniform(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / (f_in + f_out))
            Normal(0.0, std, self.seed)(var, block)

    def _dygraph_sample(self, key, shape, dtype, fan_in=None, fan_out=None):
        f_in = self.fan_in if self.fan_in is not None else fan_in
        f_out = self.fan_out if self.fan_out is not None else fan_out
        if self.uniform:
            limit = math.sqrt(6.0 / (f_in + f_out))
            return Uniform(-limit, limit)._dygraph_sample(key, shape, dtype)
        std = math.sqrt(2.0 / (f_in + f_out))
        return Normal(0.0, std)._dygraph_sample(key, shape, dtype)


class MSRA(Initializer):
    """Kaiming init (reference initializer.py:443)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        f_in, _ = _fan_in_out(var)
        f_in = self.fan_in if self.fan_in is not None else f_in
        if self.uniform:
            limit = math.sqrt(6.0 / f_in)
            Uniform(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / f_in)
            Normal(0.0, std, self.seed)(var, block)


class NumpyArrayInitializer(Initializer):
    def __init__(self, value: np.ndarray):
        self.value = np.asarray(value)

    def __call__(self, var, block):
        block.append_op(
            "assign_value",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(self.value.shape),
                "dtype": var.dtype.value,
                "values": self.value.reshape(-1).tolist(),
            },
        )


ConstantInitializer = Constant
UniformInitializer = Uniform
NormalInitializer = Normal
XavierInitializer = Xavier
MSRAInitializer = MSRA
