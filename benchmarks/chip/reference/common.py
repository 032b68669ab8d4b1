"""Pieces the family references share: seeded weights, int8 rounding,
RMSNorm and the weight matmul."""
import math

import jax
import jax.numpy as jnp
import numpy as np

#: float32 matmuls on a TPU round their operands to bfloat16 unless asked
HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed):
    """A JAX key from any non-negative integer seed (also past 2**32)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def make_params(spec, key, dtype):
    """The weights that ``spec`` describes, made from ``key`` in one jitted
    call on the default device, each cast to ``dtype``.

    ``spec`` is a nested dict whose leaves are ``(shape, init)``; ``init`` is
    one of ``("normal", scale, offset)``, ``("log_uniform", lo, hi)`` (the
    log of a uniform draw, as Mamba's ``A_log``) and ``("dt_bias", lo, hi)``
    (the inverse softplus of a log-uniform step size, as Mamba's
    ``dt_bias``)."""
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (shape, init) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            kind = init[0]
            if kind == "normal":
                x = jax.random.normal(k, shape, jnp.float32) * init[1] + init[2]
            elif kind == "log_uniform":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               init[1], init[2]))
            elif kind == "dt_bias":
                u = jax.random.uniform(k, shape, jnp.float32)
                dt = jnp.exp(u * (math.log(init[2]) - math.log(init[1]))
                             + math.log(init[1]))
                x = dt + jnp.log(-jnp.expm1(-dt))
            else:
                raise ValueError(f"unknown initialiser {kind!r}")
            out.append(x.astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(tree, jax.jit(build)(key))


def _scale(x, axis, top):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    return jnp.where(s == 0, 1.0, s)


def int8_round(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (the reduced axis of the matmul), returned in float32."""
    s = _scale(x, axis, 127.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fp8_round(x, axis):
    """float8 (e4m3) rounding with one scale per slice along ``axis``, the
    slice's largest magnitude mapped to e4m3's largest (448)."""
    s = _scale(x, axis, 448.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


#: the control's precisions: each rounds both operands of a weight matmul
ROUNDING = {"int8": int8_round, "fp8": fp8_round}


def matmul(x, w, quant):
    """x [.., K] @ w [K, N] in float32; ``quant`` ("int8", "fp8" or None)
    rounds x per row and w per column to that precision first."""
    if quant:
        x = ROUNDING[quant](x, -1)
        w = ROUNDING[quant](w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    """RMSNorm with the gain stored as an offset from one."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)
