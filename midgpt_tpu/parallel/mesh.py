"""Device mesh construction.

Replaces the reference's hardcoded ``(n_devices // 8, 8)`` 2-D mesh
(/root/reference/src/train.py:130) with an explicit 4-axis mesh
``('pipeline', 'replica', 'fsdp', 'sequence', 'tensor')`` sized from
``MeshConfig``.

- Single slice: ``mesh_utils.create_device_mesh`` lays axes out so the
  innermost (tensor) axis rides the fastest ICI links.
- Multi-slice (num_slices > 1): ``create_hybrid_device_mesh`` puts the
  outermost axes (replica) across DCN and the rest within each slice's ICI
  domain — DP-only over DCN per SURVEY.md 2.6.
"""

from __future__ import annotations

import typing as tp

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from midgpt_tpu.config import MeshConfig

AXIS_NAMES = ("pipeline", "replica", "fsdp", "sequence", "tensor")

# mesh axes a global batch is sharded over (data-parallel axes)
BATCH_AXES = ("replica", "fsdp")


def group_by_slice(
    devices: tp.Sequence, num_slices: int
) -> tp.List[tp.List]:
    """Partition devices into per-slice groups.

    Real multi-slice TPU devices carry a ``slice_index`` attribute (that is
    what ``create_hybrid_device_mesh`` keys on); grouped by it when present
    and consistent with ``num_slices``. Simulated devices (CPU, or a
    single-slice testbed standing in for N slices) have no slice_index —
    they are partitioned contiguously by listing order, which preserves the
    invariant the layout needs: each group is one DCN domain."""
    n = len(devices)
    assert n % num_slices == 0, f"{n} devices not divisible by {num_slices} slices"
    idx = {getattr(d, "slice_index", None) for d in devices}
    if None not in idx and len(idx) > 1:
        # real multi-slice DCN topology: the config MUST match it — silently
        # splitting contiguously would place ICI axes across a DCN boundary.
        # (len(idx) == 1 — all devices in one physical slice — falls through
        # to the contiguous split: that's the single-slice testbed standing
        # in for N slices.)
        assert len(idx) == num_slices, (
            f"devices report {len(idx)} physical slices {sorted(idx)} but "
            f"num_slices={num_slices}; set MeshConfig.num_slices to the "
            f"actual slice count"
        )
        groups: tp.Dict[int, tp.List] = {i: [] for i in sorted(idx)}
        for d in devices:
            groups[d.slice_index].append(d)
        out = [groups[i] for i in sorted(groups)]
        assert all(len(g) == n // num_slices for g in out), (
            f"uneven slices: {[len(g) for g in out]}"
        )
        return out
    per = n // num_slices
    return [list(devices[i * per : (i + 1) * per]) for i in range(num_slices)]


def hybrid_device_layout(
    devices: tp.Sequence, sizes: tp.Tuple[int, ...], num_slices: int
) -> np.ndarray:
    """Pure hybrid ICI/DCN mesh layout (testable without DCN hardware).

    Places the slice (DCN) dimension on the OUTERMOST positions of the
    replica axis and each slice's devices contiguously in the inner
    (fsdp, sequence, tensor) ICI axes — so only the leading ``num_slices``
    factor of 'replica' ever crosses DCN, matching the DP-only-over-DCN
    design (SURVEY.md 2.6) that ``create_hybrid_device_mesh`` produces on
    real hardware."""
    p, r, f, s, t = sizes
    assert p == 1, (
        f"pipeline axis must stay within a slice (got pipeline={p} with "
        f"num_slices={num_slices}); ppermute over DCN would serialize hops"
    )
    assert r % num_slices == 0, (
        f"replica axis {r} must be a multiple of num_slices {num_slices} "
        f"(DP-only over DCN)"
    )
    groups = group_by_slice(devices, num_slices)
    arr = np.empty((num_slices, r // num_slices, f, s, t), dtype=object)
    for i, g in enumerate(groups):
        arr[i] = np.asarray(g, dtype=object).reshape(r // num_slices, f, s, t)
    return arr.reshape(sizes)


def device_layout(
    cfg: MeshConfig, devices: tp.Sequence[jax.Device]
) -> tp.Tuple[np.ndarray, str]:
    """The device array for ``cfg`` over ``devices`` and the name of the
    branch that laid it out. TPU devices go through ``mesh_utils``, which
    knows the physical topology, and a layout it refuses is an error:
    there is no reshape behind it. Only simulated (CPU) devices, which
    have no topology, are laid out in listing order."""
    sizes = cfg.sizes(len(devices))
    if cfg.num_slices > 1:
        assert sizes[1] % cfg.num_slices == 0, (
            f"replica axis {sizes[1]} must be a multiple of num_slices "
            f"{cfg.num_slices} (DP-only over DCN)"
        )
        has_dcn = all(
            getattr(d, "slice_index", None) is not None for d in devices
        ) and len({d.slice_index for d in devices}) == cfg.num_slices
        if has_dcn:
            dcn_parallelism = (1, cfg.num_slices, 1, 1, 1)
            ici_parallelism = (
                sizes[0], sizes[1] // cfg.num_slices,
            ) + sizes[2:]
            return mesh_utils.create_hybrid_device_mesh(
                ici_parallelism,
                dcn_parallelism,
                devices=devices,
                allow_split_physical_axes=True,
            ), "create_hybrid_device_mesh"
        # simulated slices (CPU mesh / single-slice testbed): same
        # axis-split contract via the pure layout above
        return (
            hybrid_device_layout(devices, sizes, cfg.num_slices),
            "hybrid_device_layout",
        )
    if devices[0].platform == "tpu":
        return mesh_utils.create_device_mesh(
            sizes, devices=devices, allow_split_physical_axes=True
        ), "create_device_mesh"
    return np.asarray(devices).reshape(sizes), "reshape"


def create_mesh(
    cfg: MeshConfig, devices: tp.Optional[tp.Sequence[jax.Device]] = None
) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(device_layout(cfg, devices)[0], AXIS_NAMES)


def single_device_mesh(device: tp.Optional[jax.Device] = None) -> Mesh:
    """Degenerate 1-device mesh (all axes size 1) so the same sharded code
    path runs on one chip or CPU."""
    device = device if device is not None else jax.devices()[0]
    return Mesh(np.asarray([device]).reshape(1, 1, 1, 1, 1), AXIS_NAMES)
