"""Device mesh model.

≙ reference platform/place.h + platform/nccl_helper.h:81 (NCCLContextMap: the
set of devices and communicators a parallel program runs over). On TPU the
native formulation is a logical N-D mesh over the ICI torus: axes are named
(data / model / pipeline / sequence) and shardings are expressed against axis
names, so the same program scales from 1 chip to a pod by changing the mesh.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.enforce import InvalidArgumentError, enforce

# Canonical axis names. dp = data, tp = tensor/model, pp = pipeline,
# sp = sequence/context. A mesh may use any subset.
DATA_AXIS = "dp"
MODEL_AXIS = "tp"
PIPELINE_AXIS = "pp"
SEQUENCE_AXIS = "sp"


class DeviceMesh:
    """Named logical mesh over physical devices.

    Thin, stable wrapper around `jax.sharding.Mesh` so the rest of the
    framework never touches jax internals directly (the same boundary role
    pybind plays in the reference, paddle/fluid/pybind/pybind.cc:89).
    """

    def __init__(self, devices=None, axes: Optional[Dict[str, int]] = None):
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if axes is None:
            axes = {DATA_AXIS: len(devices)}
        shape = tuple(axes.values())
        n = int(np.prod(shape)) if shape else 1
        enforce(n == len(devices),
                f"mesh axes {axes} require {n} devices, got {len(devices)}",
                exc=InvalidArgumentError)
        self.axes = dict(axes)
        self._mesh = Mesh(np.asarray(devices).reshape(shape),
                          tuple(axes.keys()))

    @property
    def jax_mesh(self) -> Mesh:
        return self._mesh

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.axes.keys())

    def axis_size(self, name: str) -> int:
        return self.axes.get(name, 1)

    @property
    def num_devices(self) -> int:
        return int(np.prod(list(self.axes.values()))) if self.axes else 1

    # -- sharding constructors -------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding from a PartitionSpec-style tuple; axis names not in
        this mesh are dropped (treated as replicated) so model code can
        annotate for the most general mesh."""
        return NamedSharding(self._mesh, self.pspec(*spec))

    def pspec(self, *spec) -> PartitionSpec:
        """PartitionSpec with axis names not in this mesh dropped — lets
        model code annotate for the most general mesh and still run on a
        smaller one."""
        cleaned = []
        for s in spec:
            if s is None:
                cleaned.append(None)
            elif isinstance(s, (tuple, list)):
                kept = tuple(a for a in s if a in self.axes)
                cleaned.append(kept if kept else None)
            else:
                cleaned.append(s if s in self.axes else None)
        return PartitionSpec(*cleaned)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self._mesh, PartitionSpec())

    def batch_sharding(self, ndim: int = None) -> NamedSharding:
        """Shard dim 0 over the data axis (and sp if present for sequence
        dim is NOT assumed here — plain DP batch split, ≙ SplitLoDTensor
        feed splitting, reference parallel_executor.cc:333)."""
        if ndim is None:
            return self.sharding(DATA_AXIS)
        return self.sharding(DATA_AXIS, *([None] * (ndim - 1)))

    def __enter__(self):
        self._ctx = self._mesh.__enter__()
        return self

    def __exit__(self, *a):
        return self._mesh.__exit__(*a)

    def __repr__(self):
        return f"DeviceMesh(axes={self.axes})"


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices=None) -> DeviceMesh:
    return DeviceMesh(devices=devices, axes=axes)


_default_mesh: Optional[DeviceMesh] = None


def get_default_mesh() -> DeviceMesh:
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = DeviceMesh()
    return _default_mesh


def set_default_mesh(mesh: Optional[DeviceMesh]):
    global _default_mesh
    _default_mesh = mesh
