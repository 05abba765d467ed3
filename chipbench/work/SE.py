"""Work of a squeeze-excite stage: the pool over H and W, two FC layers
through ``reduce`` hidden units, and the channelwise scale."""


def out_shape(st, h, w, c):
    return h, w, c


def macs(st, h, w, c):
    """Multiply-accumulates for one image: the two FC layers, one per
    element for the pool and one for the scale."""
    return 2 * c * st["reduce"] + 2 * h * w * c


def n_weights(st, c):
    return 2 * c * st["reduce"] + st["reduce"] + c
