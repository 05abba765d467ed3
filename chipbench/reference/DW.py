"""Plain fp32 depthwise conv stage: ``k x k`` taps, SAME padding
(TensorFlow convention, the extra row and column at the bottom and right),
then bias and activation."""
import jax


def describe(stage):
    """The stage dict a configuration's ``blocks()`` gives for the
    program's ``chain.DW`` stage ``stage``: only square SAME taps are
    computed here."""
    if stage.hf != stage.wf or stage.padding != "same":
        raise ValueError(f"reference/DW.py computes square SAME depthwise "
                         f"convs only; the program has {stage}")
    return {"kind": "DW", "k": stage.hf, "stride": stage.stride,
            "bias": stage.bias, "act": stage.activation}


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
