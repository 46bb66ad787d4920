"""Mesh construction, sub-mesh carving, and the pod topology abstraction.

Sub-mesh carving is the mechanical substrate of EcoSched's co-scheduling:
a job assigned ``g`` allocation units gets a ``jax.sharding.Mesh`` over a
*contiguous* slice of the pod's devices (ICI contiguity — the analogue of
the paper's NUMA-domain constraint), and jobs on disjoint sub-meshes run
concurrently with zero JAX-level interaction, exactly like
``CUDA_VISIBLE_DEVICES`` partitions on a GPU node.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None) -> Mesh:
    """jax.make_mesh wrapper pinning Auto axis types (pjit-style propagation)."""
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)
    arr = np.asarray(devices).reshape(tuple(shape))
    return Mesh(arr, tuple(axes), axis_types=auto)


def carve_submesh(
    devices: Sequence, start: int, count: int, *, model_axis: int = 0
) -> Mesh:
    """A (data, model) mesh over devices[start:start+count].

    ``model_axis``: requested model-parallel width (defaults to everything
    on one axis).  Used by the co-scheduled launcher: each job gets its own
    contiguous device block.
    """
    block = list(devices[start : start + count])
    assert len(block) == count, (start, count, len(devices))
    model = model_axis or count
    assert count % model == 0, (count, model)
    return make_mesh((count // model, model), ("data", "model"), devices=block)


# ---------------------------------------------------------------------------
# Pod topology: the scheduler-facing resource model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PodTopology:
    """A multi-accelerator node/pod as EcoSched sees it.

    ``units``            M allocation units (the paper's "GPUs")
    ``chips_per_unit``   chips behind one unit (1 for a GPU node)
    ``domains``          K isolation domains (paper: NUMA sockets); at most
                         K jobs co-run, and a job's units live in
                         contiguous positions (ICI contiguity)
    """

    name: str = "tpu-v5e-pod"
    units: int = 4
    chips_per_unit: int = 64
    domains: int = 2

    @property
    def total_chips(self) -> int:
        return self.units * self.chips_per_unit

    def unit_slice(self, first_unit: int, num_units: int) -> Tuple[int, int]:
        """(device start index, device count) for a contiguous unit range."""
        return first_unit * self.chips_per_unit, num_units * self.chips_per_unit


GPU_NODE_4X = PodTopology(name="gpu-node-4x", units=4, chips_per_unit=1, domains=2)
V5E_POD_256 = PodTopology(name="v5e-pod-256", units=16, chips_per_unit=16, domains=4)
