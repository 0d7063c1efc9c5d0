"""Mesh construction for the intelligence core and the model runtime.

Axis conventions used across the framework:

  * ``data``  — GFKB index row shards / batch parallelism for trace
    classification (the intelligence-core mesh).
  * ``dp`` / ``cp`` / ``tp`` — data, context (sequence) and tensor
    parallelism for the in-tree Llama model runtime
    (kakveda_tpu.models.llama).

Mesh shape strings look like ``"data:-1"`` or ``"dp:2,cp:2,tp:2"``; a ``-1``
size absorbs all remaining devices (like a reshape wildcard).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def local_device_count() -> int:
    return len(jax.devices())


def parse_mesh_shape(spec: str, n_devices: int | None = None) -> Dict[str, int]:
    """Parse ``"dp:2,tp:-1"`` into an ordered {axis: size} dict.

    At most one axis may be -1; it is resolved so the product equals
    ``n_devices``.
    """
    n = n_devices if n_devices is not None else local_device_count()
    axes: Dict[str, int] = {}
    for part in spec.split(","):
        name, _, size = part.strip().partition(":")
        if not name or not size:
            raise ValueError(f"bad mesh axis spec: {part!r}")
        axes[name] = int(size)

    wild = [k for k, v in axes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one -1 axis allowed: {spec!r}")
    fixed = int(np.prod([v for v in axes.values() if v != -1])) if axes else 1
    if wild:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
        axes[wild[0]] = n // fixed
    elif fixed != n:
        raise ValueError(f"mesh {spec!r} wants {fixed} devices, have {n}")
    return axes


def create_mesh(
    spec: str = "data:-1",
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh from a shape spec.

    A fully-fixed spec smaller than the device count uses a prefix of the
    devices (handy for single-device paths and tests); a ``-1`` wildcard
    absorbs all of them.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if "-1" not in spec:
        fixed = int(np.prod([int(p.split(":")[1]) for p in spec.split(",")]))
        if fixed < len(devs):
            devs = devs[:fixed]
    axes = parse_mesh_shape(spec, len(devs))
    names: Tuple[str, ...] = tuple(axes.keys())
    shape: List[int] = [axes[k] for k in names]
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, axis_names=names)
