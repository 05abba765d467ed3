"""Work of a pointwise (1x1) conv stage: one GEMM of the pixels by a
``(c_in, c_out)`` matrix."""


def out_shape(st, h, w, c):
    return h, w, st["c_out"]


def macs(st, h, w, c):
    """Multiply-accumulates for one image."""
    return h * w * c * st["c_out"]


def n_weights(st, c):
    return c * st["c_out"] + (st["c_out"] if st["bias"] else 0)
