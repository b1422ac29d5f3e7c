"""Production meshes (task spec: MULTI-POD DRY-RUN step 1).

Mesh shapes are no longer hard-coded: each launch target is a
:class:`repro.core.plans.ParallelismPlan` template (plain data, device-free)
and ``mesh_from_plan`` turns one into a jax Mesh — the same object the churn
engine reshapes at runtime, so launch-time and reshard-time layouts share one
vocabulary. Importing this module never touches jax device state; devices
bind inside ``mesh_from_plan``.

Single pod: (data=16, model=16) = 256 chips; multi-pod:
(pod=2, data=16, model=16) = 512 chips. The ``pod`` axis is DP-outer (DCN);
``data`` carries DP + ZeRO-3 param sharding; ``model`` carries TP/EP.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.core.plans import ParallelismPlan

PRODUCTION_PLAN = ParallelismPlan((16, 16), ("data", "model"))
PRODUCTION_MULTI_POD_PLAN = ParallelismPlan((2, 16, 16),
                                            ("pod", "data", "model"))
DEBUG_PLAN = ParallelismPlan((2, 2), ("data", "model"))
DEBUG_MULTI_POD_PLAN = ParallelismPlan((2, 2, 2), ("pod", "data", "model"))


def mesh_from_plan(plan: ParallelismPlan,
                   devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Build the Mesh a plan describes. ``devices`` overrides jax's default
    enumeration (e.g. the elastic trainer's surviving-device list); its
    length must equal ``plan.n_devices``."""
    if devices is None:
        # Auto axes: the model code places activations with
        # with_sharding_constraint, which refuses Explicit axes (JAX 0.9's
        # make_mesh default).
        return jax.make_mesh(plan.shape, plan.axes,
                             axis_types=(AxisType.Auto,) * len(plan.axes))
    import numpy as np
    arr = np.asarray(devices, dtype=object)
    if arr.size != plan.n_devices:
        raise ValueError(f"{arr.size} devices for a {plan.shape} plan")
    return jax.sharding.Mesh(arr.reshape(plan.shape), plan.axes)


def make_production_mesh(*, multi_pod: bool = False):
    plan = PRODUCTION_MULTI_POD_PLAN if multi_pod else PRODUCTION_PLAN
    return mesh_from_plan(plan)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI-scale dry-run smoke tests (8 host devices)."""
    plan = DEBUG_MULTI_POD_PLAN if multi_pod else DEBUG_PLAN
    return mesh_from_plan(plan)
