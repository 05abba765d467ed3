"""MobileNetV2 body as published (Sandler et al. 2018, Table 2), expanded
from the (t, c, n, s) rows in the JSON file beside this one into chain
stages.

A row with t > 1 is an inverted residual: 1x1 expansion to ``t * c_in``
with ReLU6, 3x3 depthwise conv (stride s on the first repeat) with ReLU6,
linear 1x1 projection to ``c``, and the input added back where the stride
is 1 and ``c_in == c``.  The t = 1 row has no expansion.
"""


def blocks(cfg):
    out = []
    c_in = cfg["body_input"][2]
    for t, c, n, s in cfg["blocks"]:
        for i in range(n):
            stride = s if i == 0 else 1
            stages = [] if t == 1 else [
                {"kind": "PW", "c_out": c_in * t, "bias": False,
                 "act": "relu6"}]
            stages += [
                {"kind": "DW", "k": 3, "stride": stride, "bias": False,
                 "act": "relu6"},
                {"kind": "PW", "c_out": c, "bias": False, "act": None},
            ]
            out.append({"residual": stride == 1 and c_in == c,
                        "stages": stages})
            c_in = c
    return out
