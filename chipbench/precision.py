"""The control's arithmetic, one step below the configurations' bfloat16,
as fp8 training is done in practice. Every matmul and convolution operand
is rounded to float8_e4m3fn in the forward pass, and the gradient arriving
at every matmul or convolution result to float8_e5m2 before the backward
pass uses it, each tensor scaled as a whole by a power of two so that its
largest magnitude lands at the format's top (per-tensor scaling), so that
nothing underflows that a scaled recipe would keep.

Every cell's control is :data:`CONTROL`; a control of ``None`` leaves the
arithmetic as it is.
"""

CONTROL = 'fp8'
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round_float(x, dtype, top):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, jnp.exp2(jnp.floor(jnp.log2(top / amax))), 1.0)
    return (x * scale).astype(dtype).astype(x.dtype) / scale


def _forward(x):
    import jax.numpy as jnp
    return _round_float(x, jnp.float8_e4m3fn, E4M3_MAX)


def _backward(g):
    import jax.numpy as jnp
    return _round_float(g, jnp.float8_e5m2, E5M2_MAX)


def _check(control):
    if control not in (None, CONTROL):
        raise ValueError('unknown control {!r} (only {!r})'.format(
            control, CONTROL))


def operand(x, control):
    """``x`` as a matmul or convolution sees it: unchanged, or for the
    control rounded in the forward pass (the backward pass differentiates
    as if unrounded; the result's gradient is rounded by :func:`result`)."""
    _check(control)
    if control is None:
        return x
    import jax
    return x + jax.lax.stop_gradient(_forward(x) - x)


def result(y, control):
    """A matmul or convolution result: unchanged, or for the control with
    the gradient arriving at it rounded."""
    _check(control)
    if control is None:
        return y
    import jax

    @jax.custom_vjp
    def round_gradient(v):
        return v

    round_gradient.defvjp(lambda v: (v, None),
                          lambda _, g: (_backward(g),))
    return round_gradient(y)
