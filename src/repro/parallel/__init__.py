from .layout import (Layout, LayoutDelta, layout_delta, joint_axis_index,
                     psum_if, all_gather_if)
from .heads import HeadPlan, plan_heads

__all__ = ["Layout", "LayoutDelta", "layout_delta", "joint_axis_index",
           "psum_if", "all_gather_if", "HeadPlan", "plan_heads"]
