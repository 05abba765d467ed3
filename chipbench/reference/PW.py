"""Plain fp32 pointwise (1x1) conv stage: a GEMM over the channels, then
bias and activation."""
import jax
import jax.numpy as jnp


def describe(stage):
    """The stage dict a configuration's ``blocks()`` gives for the
    program's ``chain.PW`` stage ``stage``."""
    return {"kind": "PW", "c_out": stage.features, "bias": stage.bias,
            "act": stage.activation}


def params(st, c, gain):
    """{leaf: (shape, scale)}; each leaf is a standard normal draw times
    its scale, ``gain / sqrt(fan_in)`` for the matrix."""
    p = {"w": ((c, st["c_out"]), gain / c ** 0.5)}
    if st["bias"]:
        p["b"] = ((st["c_out"],), 0.1)
    return p


def apply(st, p, x, rnd):
    y = jnp.einsum("bhwc,cd->bhwd", x, rnd(p["w"]),
                   precision=jax.lax.Precision.HIGHEST)
    if st["bias"]:
        y = y + rnd(p["b"])
    return y
