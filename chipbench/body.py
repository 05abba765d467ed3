"""A configuration's body as the benchmark sees it, independent of the
program: its blocks expanded from the published table, its weights made
from the seed, its plain fp32 reference, and the algorithmic work of a
call.

Everything here is found by name: ``configs/<config>.json`` holds the
sizes, ``configs/<config>.py`` expands its table into blocks of stages,
and each stage kind ``K`` brings ``reference/K.py`` (weights and fp32
math) and ``work/K.py`` (multiply-accumulates, weights, output shape).
"""
import functools
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def sigmoid(y):
    """The logistic function; where ``exp(-y)`` overflows to inf, ``1 /
    inf`` is the limit 0."""
    return 1.0 / (1.0 + jnp.exp(-y))


def gelu_tanh(y):
    """GELU, the tanh approximation (what the program's epilogue
    applies)."""
    return 0.5 * y * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (y + 0.044715 * y ** 3)))


#: Every activation the program's epilogues accept, in plain jax.numpy.
ACTIVATIONS = {
    None: lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "relu6": lambda y: jnp.clip(y, 0.0, 6.0),
    "gelu": gelu_tanh,
    "silu": lambda y: y * sigmoid(y),
}


def load_module(path):
    """Import a file of this benchmark by its path (its name may hold a
    dot, as a configuration's does)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    with open(path) as f:
        return json.load(f)


def load_config(name):
    return load_json("configs", f"{name}.json")


def load_peak(device_kind):
    """The chip's peaks from ``peaks.json``; a kind not in the table is
    an error, never a default."""
    peaks = load_json("peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return peaks[device_kind]


def identity(a):
    return a


def round_fp8(a):
    """The control's rounding: an operand through float8 e4m3 and back."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


class Body:
    """One configuration's body at one batch size."""

    def __init__(self, cfg, batch):
        self.cfg = cfg
        self.batch = batch
        self.in_shape = tuple(cfg["body_input"])
        self.stream = jnp.dtype(cfg["stream_dtype"])
        self.blocks = load_module(os.path.join(
            HERE, "configs", f"{cfg['name']}.py")).blocks(cfg)
        kinds = {st["kind"] for b in self.blocks for st in b["stages"]}
        self.work = {k: load_module(os.path.join(HERE, "work", f"{k}.py"))
                     for k in kinds}
        self.ref = {k: load_module(os.path.join(HERE, "reference",
                                                f"{k}.py"))
                    for k in kinds}

    # -- shapes and work -------------------------------------------------

    def block_shapes(self):
        """[(input (h, w, c), output (h, w, c))] per block, one image."""
        shapes = []
        h, w, c = self.in_shape
        for b in self.blocks:
            start = (h, w, c)
            for st in b["stages"]:
                h, w, c = self.work[st["kind"]].out_shape(st, h, w, c)
            shapes.append((start, (h, w, c)))
        return shapes

    def _stage_walk(self):
        h, w, c = self.in_shape
        for b in self.blocks:
            for st in b["stages"]:
                yield st, (h, w, c)
                h, w, c = self.work[st["kind"]].out_shape(st, h, w, c)

    def macs_per_image(self):
        return sum(self.work[st["kind"]].macs(st, *hwc)
                   for st, hwc in self._stage_walk())

    def n_weights(self):
        return sum(self.work[st["kind"]].n_weights(st, hwc[2])
                   for st, hwc in self._stage_walk())

    def flops_per_call(self):
        return 2 * self.batch * self.macs_per_image()

    def bytes_per_call(self):
        """One read of each block's input, one write of its output, and
        the weights once, all at the stream width."""
        acts = sum(a[0] * a[1] * a[2] + o[0] * o[1] * o[2]
                   for a, o in self.block_shapes())
        return (self.batch * acts + self.n_weights()) * self.stream.itemsize

    def ideal_s_per_call(self, peak):
        """The least time one call could take on a chip with ``peak``:
        the larger of its FLOPs over peak FLOP/s and its bytes over peak
        HBM bandwidth."""
        return max(self.flops_per_call() / peak["bf16_flops_per_s"],
                   self.bytes_per_call() / peak["hbm_bytes_per_s"])

    # -- weights, inputs and the reference ---------------------------------

    def init_params(self, key):
        """fp32 weights, one list of stage dicts per block, cut from one
        standard normal draw (one random op compiles in a moment; one per
        leaf does not)."""
        gain = self.cfg["weight_gain"]
        leaves = [self.ref[st["kind"]].params(st, hwc[2], gain)
                  for st, hwc in self._stage_walk()]
        sizes = [math.prod(shape) for p in leaves for shape, _ in p.values()]
        z = jax.random.normal(key, (sum(sizes),))
        offset = 0
        stage_params = []
        for p in leaves:
            d = {}
            for name, (shape, scale) in p.items():
                n = math.prod(shape)
                d[name] = z[offset:offset + n].reshape(shape) * scale
                offset += n
            stage_params.append(d)
        it = iter(stage_params)
        return [[next(it) for _ in b["stages"]] for b in self.blocks]

    def make_inputs(self, key, n):
        """``n`` body inputs, the stem's ReLU6 output: N(0,1) clipped to
        [0, 6], at the stream width."""
        x = jax.random.normal(key, (n, self.batch) + self.in_shape)
        return jnp.clip(x, 0.0, 6.0).astype(self.stream)

    def forward(self, params, x, rnd=identity):
        """The plain body in fp32.  ``rnd`` is applied to what a program
        streams: each block's input, every weight and the output."""
        x = x.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            for b, bp in zip(self.blocks, params):
                x = rnd(x)
                y = x
                for st, p in zip(b["stages"], bp):
                    y = self.ref[st["kind"]].apply(st, p, y, rnd)
                    y = ACTIVATIONS[st["act"]](y)
                x = y + x if b["residual"] else y
        return rnd(x)

    @functools.cached_property
    def reference_fn(self):
        return jax.jit(self.forward)

    @functools.cached_property
    def control_fn(self):
        return jax.jit(functools.partial(self.forward, rnd=round_fp8))
