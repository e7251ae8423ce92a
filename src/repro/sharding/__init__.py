from repro.compat import make_mesh
from repro.sharding.partition import (
    Param,
    is_param,
    unbox,
    boxed_axes,
    logical_to_mesh_axes,
    named_sharding,
    tree_shardings,
    with_logical_constraint,
    activate,
    current_mesh_and_rules,
    DEFAULT_RULES,
    AxisRules,
)
