"""Plain fp32 depthwise conv stage: ``k x k`` taps, SAME padding
(TensorFlow convention, the extra row and column at the bottom and right),
then bias and activation."""
import jax


def params(st, c, gain):
    """{leaf: (shape, scale)}; each leaf is a standard normal draw times
    its scale, ``gain / sqrt(fan_in)`` for the taps."""
    p = {"f": ((st["k"], st["k"], c), gain / st["k"])}
    if st["bias"]:
        p["b"] = ((c,), 0.1)
    return p


def apply(st, p, x, rnd):
    c = x.shape[-1]
    y = jax.lax.conv_general_dilated(
        x, rnd(p["f"])[:, :, None, :],
        window_strides=(st["stride"], st["stride"]), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
        precision=jax.lax.Precision.HIGHEST)
    if st["bias"]:
        y = y + rnd(p["b"])
    return y
