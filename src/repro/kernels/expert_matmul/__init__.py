from .ops import GroupPlan, expert_matmul, group_plan
from . import kernel, ops, ref
