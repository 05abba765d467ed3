"""Plain fp32 squeeze-excite stage: the mean over H and W, an FC layer to
``reduce`` hidden units with ``hidden_act``, an FC layer back to the
incoming width, a sigmoid, and the stage input scaled channel by channel
by that gate.  The stage's output is not activated again (``act`` is
None).

``gain`` is not used: it is the body's factor that keeps the
activations' size from block to block, and the gate passes no size on.
Its weights keep the pooled activations' size into the logits instead
(He for the FC layer before the ReLU-family hidden activation,
``1 / sqrt(fan_in)`` for the one before the sigmoid), and ``b2`` is
drawn at unit scale.  So on seeded weights the gate's channels spread
across (0, 1) and do not sit all near 1, or all near 0.5, where a wrong
gate would hardly show: in a MnasNet-A1 body at gain 1.2 to 1.3 each SE
stage's 5th percentile lies at 0.04 to 0.20 and its 95th at 0.81 to
0.95 (CPU, fp32, 112x112x32 body input).
"""
import jax
import jax.numpy as jnp

from body import ACTIVATIONS, sigmoid


def describe(stage):
    """The stage dict a configuration's ``blocks()`` gives for the
    program's ``chain.SE`` stage ``stage``."""
    return {"kind": "SE", "reduce": stage.reduce,
            "hidden_act": stage.activation, "act": None}


def params(st, c, gain):
    """{leaf: (shape, scale)}; each leaf is a standard normal draw times
    its scale.  ``gain`` is not used (see above)."""
    r = st["reduce"]
    return {"w1": ((c, r), (2 / c) ** 0.5), "b1": ((r,), 0.1),
            "w2": ((r, c), 1 / r ** 0.5), "b2": ((c,), 1.0)}


def apply(st, p, x, rnd):
    hi = jax.lax.Precision.HIGHEST
    pooled = jnp.mean(x, axis=(1, 2))
    hidden = ACTIVATIONS[st["hidden_act"]](
        jnp.dot(pooled, rnd(p["w1"]), precision=hi) + rnd(p["b1"]))
    gate = sigmoid(jnp.dot(hidden, rnd(p["w2"]), precision=hi)
                   + rnd(p["b2"]))
    return x * gate[:, None, None, :]
