"""MnasNet-A1 body as published (Tan et al. 2019, Fig. 7(a); the
``mnasnet_a1`` block strings of the TensorFlow TPU reference), expanded
from the (t, c, n, s, k, se) rows in the JSON file beside this one into
chain stages.

The t = 1 row is the SepConv block: a ``k x k`` depthwise conv with ReLU
and a linear 1x1 projection to ``c``.  Every other row is an MBConv
block: 1x1 expansion to ``t * c_in`` with ReLU, ``k x k`` depthwise conv
(stride s on the first repeat) with ReLU, where ``se`` a squeeze-excite
gate (reduced to ``c_in // 4`` of the *block input* width, ReLU hidden
layer, sigmoid gate), linear 1x1 projection to ``c``, and the input
added back where the stride is 1 and ``c_in == c``.
"""


def blocks(cfg):
    out = []
    c_in = cfg["body_input"][2]
    for t, c, n, s, k, se in cfg["blocks"]:
        for i in range(n):
            stride = s if i == 0 else 1
            stages = [] if t == 1 else [
                {"kind": "PW", "c_out": c_in * t, "bias": False,
                 "act": "relu"}]
            stages.append({"kind": "DW", "k": k, "stride": stride,
                           "bias": False, "act": "relu"})
            if se:
                stages.append({"kind": "SE", "reduce": max(1, c_in // 4),
                               "hidden_act": "relu", "act": None})
            stages.append({"kind": "PW", "c_out": c, "bias": False,
                           "act": None})
            out.append({"residual": stride == 1 and c_in == c,
                        "stages": stages})
            c_in = c
    return out
