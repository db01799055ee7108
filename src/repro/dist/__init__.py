"""``repro.dist`` — declarative named-axis sharding (GSPMD idiom).

The one place sharding policy lives:

  * ``p`` / ``Axes`` / ``split_tree`` / ``retag_tree`` / ``stack_axes`` —
    tag parameters with logical axis names at creation, separate values
    from axis metadata (``repro.dist.tagging``);
  * ``Rules`` / ``param_specs`` / ``opt_state_specs`` — map logical axes
    to mesh axes per sharding mode, with divisibility fallback and the C1
    weight-update-sharding param/optimizer split (``repro.dist.sharding``);
  * ``use_rules`` / ``constrain`` — mesh-context-scoped activation
    constraints, no-ops outside a scope (``repro.dist.context``).
"""
from repro.dist.context import constrain, current_rules, use_rules
from repro.dist.rules import ACTIVATION_AXES, MODES, PARAM_AXES, build_table
from repro.dist.sharding import Rules, opt_state_specs, param_specs
from repro.dist.tagging import (
    Axes,
    p,
    retag_tree,
    split_tree,
    stack_axes,
)

__all__ = [
    "ACTIVATION_AXES",
    "Axes",
    "MODES",
    "PARAM_AXES",
    "Rules",
    "build_table",
    "constrain",
    "current_rules",
    "opt_state_specs",
    "p",
    "param_specs",
    "retag_tree",
    "split_tree",
    "stack_axes",
    "use_rules",
]
