"""The merge gain's work, counted from shapes in ``harness/roofline.py``,
against the kernel body it stands for."""

import math

import jax
import jax.numpy as jnp
import pytest

from harness import roofline

ARITH = {"add", "sub", "mul", "div", "max", "min", "log", "exp", "neg",
         "pow", "sqrt", "abs"}


def count_ops(g, c, u):
    """Arithmetic per (pair, column) and per pair in the default backend's
    body (``merge_gain_ref``), read from its jaxpr: elementwise ops at the
    [G, C, C, U] and [G, C, C] shapes, and one op per summed element."""
    from repro.kernels.ref import merge_gain_ref

    f32 = jnp.float32
    args = (jnp.zeros((g, c, u), f32), jnp.zeros((g, c), f32),
            jnp.zeros((g, c), f32), jnp.zeros((g, c), f32),
            jnp.zeros((g, u), f32), jnp.zeros((g, c), jnp.int32),
            jnp.zeros((g, c, c), f32), f32(1), f32(1))
    per_col = per_pair = 0

    def walk(jaxpr):
        nonlocal per_col, per_pair
        for eqn in jaxpr.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            name = eqn.primitive.name
            out = tuple(eqn.outvars[0].aval.shape)
            if name == "reduce_sum":
                if tuple(eqn.invars[0].aval.shape) == (g, c, c, u):
                    per_col += 1
            elif name in ARITH:
                per_col += out == (g, c, c, u)
                per_pair += out == (g, c, c)

    walk(jax.make_jaxpr(merge_gain_ref)(*args).jaxpr)
    return per_col, per_pair


def test_flop_constants_match_the_body():
    assert count_ops(3, 5, 7) == (roofline.FLOPS_PER_PAIR_COLUMN,
                                  roofline.FLOPS_PER_PAIR)


def test_bytes_are_the_operands_and_outputs():
    from repro.kernels import ops as kops

    g, c, u = 3, 5, 7
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((g, c, u), f32),
            *(jax.ShapeDtypeStruct((g, c), f32) for _ in range(3)),
            jax.ShapeDtypeStruct((g, u), f32),
            jax.ShapeDtypeStruct((g, c), jnp.int32),
            jax.ShapeDtypeStruct((g, c, c), f32))
    outs = jax.eval_shape(kops.merge_gain, *args, f32(1), f32(1))
    nbytes = sum(math.prod(a.shape) * a.dtype.itemsize
                 for a in (*args, *outs))
    assert roofline.merge_gain_bytes(g, c, u) == nbytes


def test_share_and_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.roofline_share(50.0, 20.0, 4.0, peak) == (50.0, "bytes")
    assert roofline.roofline_share(400.0, 20.0, 8.0, peak) == (50.0, "flops")


def test_peaks_by_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
