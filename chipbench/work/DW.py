"""Work of a depthwise conv stage: ``k x k`` taps per output element at
the incoming width, SAME padding."""


def out_shape(st, h, w, c):
    s = st["stride"]
    return -(-h // s), -(-w // s), c


def macs(st, h, w, c):
    """Multiply-accumulates for one image."""
    ho, wo, _ = out_shape(st, h, w, c)
    return ho * wo * c * st["k"] * st["k"]


def n_weights(st, c):
    return st["k"] * st["k"] * c + (c if st["bias"] else 0)
