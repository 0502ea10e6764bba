from .generate import (
    banded_lower,
    chain_matrix,
    ic0_factor,
    lung2_like,
    poisson2d,
    random_lower,
    refresh_values,
    serve_traffic,
    stencil27,
    hpcg_hierarchy,
)
from .faults import (
    FAULT_KINDS,
    VALUE_FAULTS,
    diag_positions,
    inject_values,
    wrong_pattern,
)
from .pathological import PATHOLOGICAL_PATTERNS, diag_condition, pathological

__all__ = [
    "FAULT_KINDS",
    "VALUE_FAULTS",
    "diag_positions",
    "inject_values",
    "wrong_pattern",
    "banded_lower",
    "chain_matrix",
    "ic0_factor",
    "lung2_like",
    "poisson2d",
    "random_lower",
    "refresh_values",
    "serve_traffic",
    "stencil27",
    "hpcg_hierarchy",
    "PATHOLOGICAL_PATTERNS",
    "diag_condition",
    "pathological",
]
