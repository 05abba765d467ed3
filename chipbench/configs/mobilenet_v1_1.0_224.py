"""MobileNetV1 body as published (Howard et al. 2017, Table 1), expanded
from the table in the JSON file beside this one into chain stages.

Each block is a 3x3 depthwise conv (stride s) and a 1x1 pointwise conv to
``c_out``, each followed by its folded batch norm (a bias) and ReLU6.
"""


def blocks(cfg):
    out = []
    for c_out, stride in cfg["blocks"]:
        out.append({"residual": False, "stages": [
            {"kind": "DW", "k": 3, "stride": stride, "bias": True,
             "act": "relu6"},
            {"kind": "PW", "c_out": c_out, "bias": True, "act": "relu6"},
        ]})
    return out
