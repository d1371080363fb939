"""Device-mesh helpers: the TPU-native stand-in for the reference's
rank-based multi-GPU coordination.

The reference coordinates multi-node training purely by static input sharding
(``cur_shard=rank, shard_count=world`` — ``petastorm/reader.py:485-502``,
SURVEY.md §5.8). Here the same rule is keyed by ``jax.process_index()`` /
``jax.process_count()``, and cross-chip data movement is XLA's ICI/DCN via
``jax.sharding`` — never hand-rolled collectives.
"""

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def process_shard():
    """``(cur_shard, shard_count)`` for this host — feed to make_reader.

    Parity target: BASELINE.json north-star ("cur_shard=jax.process_index()").
    """
    return jax.process_index(), jax.process_count()


class DeviceShardPlan(object):
    """Per-device slicing of a batch-dim-sharded host batch.

    ``devices[k]`` receives local rows ``bounds[k] = (start, stop)``; the
    staged shards stitch into the global array with
    ``jax.make_array_from_single_device_arrays(global_shape, sharding,
    shards)``. Because host batches are C-contiguous with a leading batch
    dim, every bound is a zero-copy contiguous sub-slice — the layout is
    computed once per (sharding, shape) and costs nothing per batch.
    """

    __slots__ = ('devices', 'bounds', 'global_shape')

    def __init__(self, devices, bounds, global_shape):
        self.devices = tuple(devices)
        self.bounds = tuple(bounds)
        self.global_shape = tuple(global_shape)

    @property
    def n_devices(self):
        return len(self.devices)


def device_shard_plan(sharding, local_shape, process_count=None):
    """Plan per-device shard assembly for one field, or ``None``.

    Eligibility: the sharding partitions (at most) the leading batch dim —
    every addressable device's index is a unit-stride row range covering
    all non-batch dims — and the distinct row ranges are equal-sized and
    exactly tile the ``local_shape[0]`` host rows. Replication (e.g. a
    ``('data', 'model')`` mesh with the batch only on ``'data'``) is fine:
    replica devices share a bound and each receives its own put of the
    same sub-slice. Anything else (a sequence-sharded dim, uneven
    partitions, addressable shards that don't tile the local batch)
    returns ``None`` and the caller keeps the one-shot
    ``make_array_from_process_local_data`` path.

    Multi-host: the global batch is ``local_rows * process_count`` and the
    k-th distinct addressable row range (in global order) maps to the k-th
    local sub-slice — the same local-rows-in-global-order rule
    ``make_array_from_process_local_data`` applies, so the two paths stage
    identical global arrays.
    """
    local_shape = tuple(local_shape)
    if not local_shape or local_shape[0] <= 0:
        return None
    if process_count is None:
        process_count = jax.process_count()
    global_shape = (local_shape[0] * int(process_count),) + local_shape[1:]
    try:
        indices_map = sharding.addressable_devices_indices_map(global_shape)
    except (AttributeError, ValueError, TypeError):
        return None
    if not indices_map:
        return None
    entries = []
    for device, index in indices_map.items():
        if index is None:
            index = ()
        if not isinstance(index, tuple):
            index = (index,)
        if len(index) > len(global_shape):
            return None
        # Non-batch dims must be unsharded (full slices).
        for dim, idx in zip(global_shape[1:], index[1:]):
            if not isinstance(idx, slice):
                return None
            if idx.step not in (None, 1):
                return None
            if (idx.start not in (None, 0)
                    or idx.stop not in (None, dim)):
                return None
        lead = index[0] if index else slice(None)
        if not isinstance(lead, slice) or lead.step not in (None, 1):
            return None
        start = 0 if lead.start is None else int(lead.start)
        stop = global_shape[0] if lead.stop is None else int(lead.stop)
        if stop <= start:
            return None
        entries.append((device, start, stop))
    distinct = sorted({(start, stop) for _, start, stop in entries})
    sizes = {stop - start for start, stop in distinct}
    if len(sizes) != 1:
        return None
    shard_rows = sizes.pop()
    if shard_rows * len(distinct) != local_shape[0]:
        # The addressable shards must exactly tile this host's rows.
        return None
    local_bounds = {span: (k * shard_rows, (k + 1) * shard_rows)
                    for k, span in enumerate(distinct)}
    devices = [device for device, _, _ in entries]
    bounds = [local_bounds[(start, stop)] for _, start, stop in entries]
    return DeviceShardPlan(devices, bounds, global_shape)


def make_mesh(axis_shapes, devices=None):
    """Build a ``Mesh`` from ``{'axis': size}`` (``-1`` = fill with remaining).

    Example: ``make_mesh({'data': -1, 'model': 2})`` on 8 devices gives a
    (4, 2) mesh with axes ('data', 'model').

    Not topology-aware: ``jax.devices()`` is reshaped in enumeration order.
    For a ``'data'``-only mesh every order is equivalent (one all-reduce
    over all chips); a mesh whose inner axis should sit on ICI neighbours
    needs ``jax.experimental.mesh_utils.create_device_mesh`` instead.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axis_shapes)
    sizes = list(axis_shapes.values())
    if sizes.count(-1) > 1:
        raise ValueError('At most one axis may be -1')
    known = int(np.prod([s for s in sizes if s != -1]))
    if len(devices) % known:
        raise ValueError('{} devices not divisible by fixed axes {}'.format(
            len(devices), axis_shapes))
    sizes = [len(devices) // known if s == -1 else s for s in sizes]
    if int(np.prod(sizes)) != len(devices):
        raise ValueError('Mesh {} does not cover {} devices'.format(
            dict(zip(names, sizes)), len(devices)))
    device_array = np.asarray(devices).reshape(sizes)
    return Mesh(device_array, tuple(names))


def batch_sharding(mesh, batch_axes='data'):
    """NamedSharding placing the leading (batch) dim on ``batch_axes``.

    Remaining dims are replicated — the standard data-parallel input layout.
    """
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    return NamedSharding(mesh, PartitionSpec(tuple(batch_axes)))


def replicated_sharding(mesh):
    return NamedSharding(mesh, PartitionSpec())


def sequence_sharding(mesh, batch_axis='data', seq_axis='model', seq_dim=1):
    """NamedSharding for long-context inputs: batch dim on ``batch_axis``,
    sequence dim (``seq_dim``) on ``seq_axis``, rest replicated.

    The layout ring attention (``models/attention.py``) consumes: each device
    holds a ``[B/dp, T/sp, ...]`` tile, kv blocks rotate over ``seq_axis``'s
    ICI ring. Use as ``JaxLoader(..., sharding={'tokens': sequence_sharding(
    mesh)})`` (per-field dict: only sequence fields shard the T dim; labels
    etc. keep ``batch_sharding``).
    """
    if seq_dim < 1:
        raise ValueError('seq_dim must be >= 1 (0 is the batch dim)')
    spec = [None] * (seq_dim + 1)
    spec[0] = batch_axis
    spec[seq_dim] = seq_axis
    return NamedSharding(mesh, PartitionSpec(*spec))
