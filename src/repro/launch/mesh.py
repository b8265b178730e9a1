"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run driver must set XLA_FLAGS *before*
any jax initialization).
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_agent_mesh", "place_agents",
           "SINGLE_POD_SHAPE", "MULTI_POD_SHAPE"]

SINGLE_POD_SHAPE = (16, 16)            # 256 chips / pod (TPU v5e)
MULTI_POD_SHAPE = (2, 16, 16)          # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the step is written for GSPMD propagation, not for
    # sharding-in-types (jax.make_mesh's default axis type is Explicit)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_agent_mesh(num_agents: int, devices=None) -> jax.sharding.Mesh | None:
    """A 1-D ``("data",)`` mesh over ``devices`` (default: all of them) that
    the agent axis is sharded over, K / len(devices) whole agents per
    device — or ``None`` where there is nothing to shard: one device, or a
    K the device count does not divide."""
    devices = jax.devices() if devices is None else list(devices)
    if len(devices) < 2 or num_agents % len(devices):
        return None
    return jax.sharding.Mesh(np.array(devices), ("data",))


def place_agents(tree, mesh: jax.sharding.Mesh, *, num_agents: int,
                 agent_dim: int = 0):
    """Put every leaf of ``tree`` on ``mesh``: leaves whose dimension
    ``agent_dim`` is the agent axis (size ``num_agents``) are sharded on it
    over ``"data"`` (:func:`repro.sharding.rules.agent_stack_pspec`), the
    rest are replicated.  ``agent_dim=1`` places a (T, K, ...) block
    batch."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding.rules import agent_stack_pspec

    def sharding(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) <= agent_dim or shape[agent_dim] != num_agents:
            return NamedSharding(mesh, PartitionSpec())
        spec = agent_stack_pspec(mesh, "data", num_agents=num_agents,
                                 ndim=len(shape) - agent_dim)
        return NamedSharding(mesh, PartitionSpec(*([None] * agent_dim),
                                                 *spec))

    return jax.device_put(tree, jax.tree.map(sharding, tree))
