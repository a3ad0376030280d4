from . import multihost
from .collectives import pmean, psum, all_gather, reduce_scatter, ppermute_ring
from .context import (
    make_ring_attention,
    make_ulysses_attention,
    ring_attention,
    ulysses_attention,
)
from .dp import TrainState, make_train_step, make_eval_step, make_train_step_shardmap
from . import zero1
from . import zero1_fused
from .zero1_fused import (
    fused_adam_update,
    make_train_step_zero1_fused,
    zero1_fused_state,
)
from .zero1 import (
    make_train_step_zero1,
    make_train_step_zero1_shardmap,
    zero1_optimizer,
    zero1_state,
    zero1_state_shardings,
)
from .ep import (
    held_experts_apply,
    moe_apply,
    router_dispatch,
    router_dispatch_expert_choice,
    sigmoid_route,
    stack_expert_params,
)
from .pp import make_train_step_pp, pipeline_apply, stack_stage_params, switch_stage
from .pp_1f1b import build_schedule, make_train_step_1f1b, pipeline_grads_1f1b
from . import pp_plan
from .pp_plan import PipelinePlan, plan_from_model, plan_from_profile, plan_stages
from . import rules
from .rules import (
    RULE_TABLES,
    ShardLargest,
    match_partition_rules,
    rules_for_model,
    with_fsdp,
)
from . import layout
from .layout import Layout, LayoutError, layout_candidates, resolve_layout

__all__ = [
    "multihost",
    "pmean",
    "psum",
    "all_gather",
    "reduce_scatter",
    "ppermute_ring",
    "TrainState",
    "make_train_step",
    "make_eval_step",
    "make_train_step_shardmap",
    "zero1",
    "zero1_fused",
    "fused_adam_update",
    "make_train_step_zero1_fused",
    "zero1_fused_state",
    "make_train_step_zero1",
    "make_train_step_zero1_shardmap",
    "zero1_optimizer",
    "zero1_state",
    "zero1_state_shardings",
    "ring_attention",
    "make_ring_attention",
    "ulysses_attention",
    "make_ulysses_attention",
    "pipeline_apply",
    "make_train_step_pp",
    "build_schedule",
    "pipeline_grads_1f1b",
    "make_train_step_1f1b",
    "stack_stage_params",
    "switch_stage",
    "PipelinePlan",
    "plan_stages",
    "plan_from_profile",
    "plan_from_model",
    "pp_plan",
    "moe_apply",
    "sigmoid_route",
    "held_experts_apply",
    "router_dispatch_expert_choice",
    "router_dispatch",
    "stack_expert_params",
    "rules",
    "RULE_TABLES",
    "ShardLargest",
    "match_partition_rules",
    "rules_for_model",
    "with_fsdp",
    "layout",
    "Layout",
    "LayoutError",
    "layout_candidates",
    "resolve_layout",
]
