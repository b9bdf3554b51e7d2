"""Packed host<->device state transfer.

Every per-leaf ``device_get`` is a sequential host<->device round
trip; fetching a 125-analyzer plan's ~250 state leaves one by one pays
that latency ~250 times while the actual payload is a few kilobytes. The fix: the traced epilogue concatenates every state
leaf into ONE 1-D array per dtype (``pack_tree``), the host fetches that
handful of arrays in one ``device_get``, and ``unpack_tree`` slices the
flat buffers back into the original pytree using a host-side template —
the template is always known (init states are host numpy; lax.scan
carries preserve shape/dtype exactly).

Reference analog: none — Spark collects one aggregated Row per job
(SURVEY.md §3.1 ★#1); this restores that "one result row" property
for the device.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class DeviceResident:
    """Marker wrapper for a pytree leaf ``packed_device_get`` must NOT
    fetch. The fused scan wraps collector op states (device-resident
    spill key buffers, megabytes of u64 keys) in this before the
    epilogue fetch: the wrapper is not registered as a pytree node, so
    it flattens as an opaque leaf and — not being a ``jax.Array`` —
    passes through the packed transfer untouched. The buffers stay in
    device memory for the post-scan sort finalize (analyzers/spill.py)."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


def _canonical_dtype_name(dtype) -> str:
    return np.dtype(jax.dtypes.canonicalize_dtype(dtype)).name


def _shape_dtype(leaf) -> Tuple[Tuple[int, ...], Any]:
    """(shape, dtype) without materializing device values."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        arr = np.asarray(leaf)  # plain Python scalar/sequence: host-side
        shape, dtype = arr.shape, arr.dtype
    return tuple(shape), dtype


def pack_tree(tree: Any) -> Dict[str, jnp.ndarray]:
    """Traced: concatenate all leaves into one 1-D array per dtype.

    Leaves are raveled and concatenated in ``tree_leaves`` order, so the
    host can slice them back out against any structurally-equal template.
    """
    groups: Dict[str, list] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = jnp.asarray(leaf)
        groups.setdefault(_canonical_dtype_name(arr.dtype), []).append(
            arr.ravel()
        )
    return {
        name: parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        for name, parts in groups.items()
    }


def unpack_tree(packed: Dict[str, np.ndarray], template: Any) -> Any:
    """Host: slice the fetched flat buffers back into ``template``'s
    structure. ``template`` leaves only need ``.shape``/``.dtype``
    (numpy arrays, scalars, or ``jax.ShapeDtypeStruct`` all work)."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    offsets = {name: 0 for name in packed}
    out = []
    for leaf in leaves:
        shape, dtype = _shape_dtype(leaf)
        name = _canonical_dtype_name(dtype)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        off = offsets[name]
        flat = np.asarray(packed[name][off:off + size])
        offsets[name] = off + size
        out.append(flat.reshape(shape) if shape else flat.reshape(())[()])
    return jax.tree_util.tree_unflatten(treedef, out)


def scan_output_template(
    init_states: Tuple[Any, ...], host_slots, nb: int
) -> Tuple[Any, Any]:
    """Shape/dtype template for the fused scan's packed output
    ``(final_states, ys)``: final states mirror the init states (scan
    carries preserve shape/dtype); each host-slot y is that op's state
    with a leading ``nb`` (stacked per-batch outputs)."""

    def struct(leaf, lead: Tuple[int, ...] = ()):
        # shape/dtype attributes only — np.asarray on a DEVICE leaf
        # would fetch its value (a device round trip per leaf, the very
        # cost this module exists to remove)
        shape, dtype = _shape_dtype(leaf)
        return jax.ShapeDtypeStruct(
            lead + shape, jax.dtypes.canonicalize_dtype(dtype)
        )

    finals = jax.tree_util.tree_map(struct, init_states)
    ys = tuple(
        jax.tree_util.tree_map(lambda l: struct(l, (nb,)), init_states[i])
        for i in host_slots
    )
    return finals, ys


def packed_device_get(tree: Any) -> Any:
    """Fetch an arbitrary device pytree in one transfer per dtype.

    Generic helper for paths that don't fold the pack into their own
    jitted program. Runs EAGERLY (ravel + concatenate dispatches, no
    jit): a jitted pack would recompile for every distinct leaf count —
    e.g. a streaming run's pending host-fold outputs scale with the
    batch count. Host-side leaves (numpy, Python scalars) and
    :class:`DeviceResident`-wrapped leaves pass through untouched; only
    bare ``jax.Array`` leaves are packed and fetched."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    device_idx = [
        i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.Array)
    ]
    if not device_idx:
        return tree
    from deequ_tpu.telemetry import get_telemetry

    get_telemetry().counter("engine.device_fetches").inc()
    groups: Dict[str, list] = {}
    group_members: Dict[str, list] = {}
    for i in device_idx:
        name = _canonical_dtype_name(leaves[i].dtype)
        groups.setdefault(name, []).append(jnp.ravel(leaves[i]))
        group_members.setdefault(name, []).append(i)
    packed = jax.device_get(
        {
            name: parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            for name, parts in groups.items()
        }
    )
    # bytes actually pulled over the link per sync — together with
    # engine.device_fetches this is the sync-discipline audit surface
    # (tests/test_sync_discipline.py pins fetches; dashboards trend
    # bytes/fetch to catch a state blow-up before it costs seconds)
    get_telemetry().counter("engine.fetch_bytes").inc(
        # lint-ok: trace-hazard: post-device_get accounting — `packed`
        # is host numpy here; this IS the sanctioned sync epilogue
        int(sum(np.asarray(a).nbytes for a in packed.values()))
    )
    out = list(leaves)
    for name, members in group_members.items():
        off = 0
        flat = packed[name]
        for i in members:
            shape = tuple(leaves[i].shape)
            # lint-ok: trace-hazard: static shape arithmetic on the
            # host side of the epilogue
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            # lint-ok: trace-hazard: slicing the already-fetched host
            # buffer back into per-leaf views
            piece = np.asarray(flat[off:off + size])
            off += size
            out[i] = piece.reshape(shape) if shape else piece.reshape(())[()]
    return jax.tree_util.tree_unflatten(treedef, out)
