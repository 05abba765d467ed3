"""Core: the paper's DWConv/PWConv contributions as composable framework ops,
the declarative separable-chain API (spec -> plan -> lower -> execute), and
the whole-network engine (NetworkSpec -> NetworkPlan -> execute_network)."""
from repro.core.chain import (
    DW,
    PW,
    SeparableSpec,
    execute,
    init_chain,
    inverted_residual_spec,
    lower,
    plan,
    separable_block_spec,
)
from repro.core.dwconv import depthwise2d
from repro.core.network import (
    NetworkPlan,
    NetworkSpec,
    cast_network_params,
    execute_network,
    init_network,
    mobilenet_v1_spec,
    mobilenet_v2_spec,
    plan_network,
    tune_network,
)
from repro.core.pwconv import DEFAULT_POLICY, KernelPolicy, pointwise
from repro.core.separable import (
    init_inverted_residual,
    init_separable,
    inverted_residual,
    separable_block,
)
from repro.kernels.policy import BF16_STREAM, DtypePolicy

__all__ = [
    "BF16_STREAM",
    "DEFAULT_POLICY",
    "DW",
    "DtypePolicy",
    "KernelPolicy",
    "NetworkPlan",
    "NetworkSpec",
    "PW",
    "SeparableSpec",
    "cast_network_params",
    "execute_network",
    "init_network",
    "mobilenet_v1_spec",
    "mobilenet_v2_spec",
    "plan_network",
    "tune_network",
    "depthwise2d",
    "execute",
    "init_chain",
    "init_inverted_residual",
    "init_separable",
    "inverted_residual",
    "inverted_residual_spec",
    "lower",
    "plan",
    "pointwise",
    "separable_block",
    "separable_block_spec",
]
