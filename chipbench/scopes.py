"""From the compiled program's text to the block and scopes of each
instruction.

The program names its parts with ``jax.named_scope``: each block ``b00``
... ``bNN`` (``core/network.build_network_fn``), each segment by its kind
(``kernels/lowering``: ``fused3``, ``fused2``, ``pw``, ...), each SAME pad
``same_pad`` (``kernels/ops.pad_same``), and each Pallas kernel by its
``name=``.  The names reach the compiled HLO as

    %pad.2 = bf16[...] pad(...), metadata={op_name=
        "jit(run)/b01/fused3/same_pad/jit(_pad)/pad" ...}

while the profiler's device op events carry only the instruction's name
(``pad.2``).  This map is how a device op is charged to a block.
"""
import re

#: The block of an instruction outside every block scope: the input's
#: relayout, the weights' copies.
UNSCOPED = "unscoped"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                    r'\bop_name="((?:[^"\\]|\\.)*)"', re.MULTILINE)
_KERNEL = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*'
                     r'custom_call_target="tpu_custom_call"', re.MULTILINE)
_BLOCK = re.compile(r"b\d{2,}")


def parse_op_name(op_name):
    """``"jit(run)/b01/fused3/same_pad/jit(_pad)/pad"`` ->
    ``("b01", ("fused3", "same_pad"))``: the block, then the named scopes
    inside it, outermost first (``jit(...)`` levels and the primitive
    left out).  Outside every block: ``("unscoped", <its scopes>)``."""
    names = [p for p in op_name.split("/")[:-1] if not p.startswith("jit(")]
    for i, p in enumerate(names):
        if _BLOCK.fullmatch(p):
            return p, tuple(names[i + 1:])
    return UNSCOPED, tuple(names)


def scope_map(hlo_text):
    """{instruction name: (block, scopes)} for every instruction of the
    compiled text that carries an ``op_name``."""
    return {m.group(1): parse_op_name(m.group(2))
            for m in _INSTR.finditer(hlo_text)}


def kernels(hlo_text):
    """The names of the compiled text's ``tpu_custom_call``
    instructions: the Pallas kernels."""
    return _KERNEL.findall(hlo_text)


def block_key(scoped):
    """``("b01", ("fused3", "same_pad"))`` -> ``"b01.fused3"``: the block
    and its segment kind; ``"unscoped"`` outside every block."""
    block, names = scoped
    if block == UNSCOPED or not names:
        return block
    return f"{block}.{names[0]}"
