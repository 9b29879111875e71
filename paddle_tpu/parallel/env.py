"""Mesh / collective-context registry.

Reference parity: `paddle/fluid/platform/collective_helper.h:50-108` keys
NCCL communicators by `ring_id`; `nccl_helper.h:92` holds the context map.
TPU-native: a ring is a *named mesh axis* of a `jax.sharding.Mesh`. During
shard_map lowering the active axis map is pushed here so collective ops can
emit `lax.psum(..., axis_name)`; outside any mesh they degrade to identity
(single-chip semantics).
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Optional

__all__ = [
    "DCN_AXIS", "ICI_AXIS", "MODEL_AXIS", "set_global_mesh",
    "global_mesh", "register_ring", "ring_info", "collective_scope",
    "active_axes", "axis_size_compat", "shard_map_compat",
    "axis_name_for_ring", "axis_size_for_ring", "dcn_replicas",
    "model_parallel_degree", "create_hybrid_mesh", "MeshHierarchy",
    "mesh_hierarchy", "trainer_id", "trainer_num",
    "trainer_endpoints", "current_endpoint",
]

_tls = threading.local()

# ring_id -> (axis_name, axis_size). Global registry, mirrors
# NCCLCommContext's ring registry.
_RINGS: Dict[int, tuple] = {}

_GLOBAL_MESH = None


def set_global_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def global_mesh():
    return _GLOBAL_MESH


def register_ring(ring_id: int, axis_name: str, axis_size: int):
    """TPU analogue of CCommInitOp: bind a ring id to a mesh axis."""
    _RINGS[int(ring_id)] = (axis_name, int(axis_size))


def ring_info(ring_id: int):
    return _RINGS.get(int(ring_id))


@contextlib.contextmanager
def collective_scope(active_axes):
    """Mark mesh axes as live (inside shard_map) for collective lowering.

    active_axes: dict axis_name -> axis_size.
    """
    prev = getattr(_tls, "axes", None)
    _tls.axes = dict(active_axes)
    try:
        yield
    finally:
        _tls.axes = prev


def active_axes() -> Optional[dict]:
    return getattr(_tls, "axes", None)


def axis_size_compat(axis_name):
    """Size of a live mesh axis at trace time (no runtime collective)."""
    from jax import lax

    return lax.axis_size(axis_name)


def shard_map_compat(f, mesh, in_specs, out_specs, check_vma=False):
    """The one spelling of `jax.shard_map` every call in the tree uses
    (check_vma off by default: the lowerings place their own
    collectives)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_name_for_ring(ring_id: int):
    """Axis name (or TUPLE of names for a ring spanning the hybrid
    (dcn, ici) pair — jax collectives accept tuple axis names) bound to
    `ring_id`, or None when the ring's axes are not live."""
    axes = active_axes()
    if not axes:
        return None
    info = _RINGS.get(int(ring_id))
    if info is None:
        # Default ring 0 = the sole active axis if unambiguous — or the
        # whole hybrid (dcn, ici) pair, which together IS the dp world.
        if int(ring_id) == 0:
            if len(axes) == 1:
                return next(iter(axes))
            if set(axes) == {DCN_AXIS, ICI_AXIS}:
                return (DCN_AXIS, ICI_AXIS)
            # tensor-parallel factorization: ring 0 is still the DATA
            # world — the (dcn, replica) pair. The model axis never
            # joins a dp ring (its collectives are the TP engine's).
            if set(axes) == {DCN_AXIS, ICI_AXIS, MODEL_AXIS}:
                return (DCN_AXIS, ICI_AXIS)
        return None
    name = info[0]
    if isinstance(name, (tuple, list)):
        name = tuple(name)
        return name if all(a in axes for a in name) else None
    return name if name in axes else None


def axis_size_for_ring(ring_id: int) -> int:
    axes = active_axes() or {}
    name = axis_name_for_ring(ring_id)
    if name is None:
        return 1
    if isinstance(name, tuple):
        size = 1
        for a in name:
            size *= axes[a]
        return size
    return axes[name]


# -- hybrid DCN+ICI mesh (multi-pod data parallelism) ------------------------
#
# A multi-pod TPU cluster has two interconnect tiers: ICI inside each
# pod (fast) and DCN between pods (slow — it bounds grad-sync time at
# scale, Kumar et al. 1909.09756 §5). The t5x/maxtext idiom
# (`jax.experimental.mesh_utils.create_hybrid_device_mesh`,
# SNIPPETS.md [1]/[2]) factors the data-parallel world into a 2-D
# (dcn, ici) mesh so collectives can lower hierarchically:
# reduce-scatter inside the pod over ICI, exchange only 1/ici_size of
# the gradient bytes across pods over DCN, all-gather inside the pod.

#: mesh axis names of the hybrid factorization; DCN_AXIS is the major
#: (slow, cross-pod) axis, ICI_AXIS the minor (fast, intra-pod) one.
#: With FLAGS_tpu_model_parallel > 1 the intra-pod tier factors once
#: more into (replica, model): ICI_AXIS keeps its name but becomes the
#: data-parallel REPLICA axis, and MODEL_AXIS is the new innermost
#: (fastest-hop) axis tensor-parallel params shard over.
DCN_AXIS = "dcn"
ICI_AXIS = "ici"
MODEL_AXIS = "model"


def dcn_replicas(default=1) -> int:
    """The requested number of DCN replicas (pods) in the dp
    factorization: `FLAGS_tpu_dcn_replicas` when set (> 0), else the
    `PADDLE_NUM_PODS` launch env, else `default` (1 = flat dp — the
    byte-for-byte pre-hybrid lowering)."""
    from ..utils.flags import get_flag

    v = get_flag("FLAGS_tpu_dcn_replicas", 0)
    try:
        v = int(v or 0)
    except (TypeError, ValueError):
        v = 0
    if v > 0:
        return v
    try:
        return int(os.environ.get("PADDLE_NUM_PODS", "") or default)
    except ValueError:
        return default


def model_parallel_degree(default=1) -> int:
    """The requested tensor-parallel (model) degree:
    `FLAGS_tpu_model_parallel` when set (> 0), else the
    `PADDLE_MP_DEGREE` launch env (exported by `launch --mp_degree`),
    else `default` (1 = no tensor parallelism — today's lowering,
    byte-for-byte)."""
    from ..utils.flags import get_flag

    v = get_flag("FLAGS_tpu_model_parallel", 0)
    try:
        v = int(v or 0)
    except (TypeError, ValueError):
        v = 0
    if v > 0:
        return v
    try:
        return int(os.environ.get("PADDLE_MP_DEGREE", "") or default)
    except ValueError:
        return default


def create_hybrid_mesh(nranks=None, dcn=None, mp=None, devices=None):
    """The hybrid `jax.sharding.Mesh` over `nranks` devices, or None
    when no factorization applies (the caller falls back to the flat
    1-D mesh, never a wrong mesh).

    Without tensor parallelism (mp <= 1): the 2-D (dcn, ici) mesh when
    dcn > 1 divides the world, else None — byte-for-byte the
    pre-model-parallel behavior. With `FLAGS_tpu_model_parallel` /
    `PADDLE_MP_DEGREE` > 1: the intra-pod tier factors into
    (replica, model), giving a 3-D (dcn, ici, model) mesh — `model` is
    the INNERMOST axis, so on the row-major CPU/emulation layout a
    model group is a contiguous device block riding the fastest ICI
    hops (the Megatron/t5x placement). The dcn axis is kept even at
    dcn == 1 so every consumer reads one mesh shape.

    On real multi-pod TPU the device order comes from
    `mesh_utils.create_hybrid_device_mesh` (DCN-connectivity aware);
    on CPU/emulation (and single-slice TPU) the devices reshape
    row-major — pod p owns the contiguous block [p*ici, (p+1)*ici)."""
    import warnings

    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if nranks is not None:
        devices = devices[:nranks]
    n = len(devices)
    dcn = int(dcn if dcn is not None else dcn_replicas())
    mp = int(mp if mp is not None else model_parallel_degree())
    dcn = max(dcn, 1)
    if n <= 1:
        return None
    if mp > 1:
        if n % (dcn * mp) != 0:
            warnings.warn(
                "hybrid mesh: %d device(s) not divisible by "
                "dcn=%d x mp=%d; falling back to the flat dp mesh"
                % (n, dcn, mp))
            return None
        replica = n // (dcn * mp)
        dev_arr = None
        if devices[0].platform == "tpu":
            try:
                from jax.experimental import mesh_utils

                dev_arr = mesh_utils.create_hybrid_device_mesh(
                    (1, replica, mp), (dcn, 1, 1), devices=devices)
            except Exception as e:  # noqa: BLE001 - single-slice
                warnings.warn(
                    "create_hybrid_device_mesh failed (%s); using "
                    "row-major pod blocks" % (e,))
        if dev_arr is None:
            dev_arr = np.array(devices).reshape(dcn, replica, mp)
        return Mesh(dev_arr, (DCN_AXIS, ICI_AXIS, MODEL_AXIS))
    if dcn <= 1:
        return None
    if n % dcn != 0:
        warnings.warn(
            "hybrid mesh: %d device(s) not divisible by "
            "FLAGS_tpu_dcn_replicas=%d; falling back to the flat dp "
            "mesh" % (n, dcn))
        return None
    ici = n // dcn
    dev_arr = None
    if devices[0].platform == "tpu":
        try:
            from jax.experimental import mesh_utils

            dev_arr = mesh_utils.create_hybrid_device_mesh(
                (1, ici), (dcn, 1), devices=devices)
        except Exception as e:  # noqa: BLE001 - single-slice
            warnings.warn(
                "create_hybrid_device_mesh failed (%s); using "
                "row-major pod blocks" % (e,))
    if dev_arr is None:
        dev_arr = np.array(devices).reshape(dcn, ici)
    return Mesh(dev_arr, (DCN_AXIS, ICI_AXIS))


class MeshHierarchy(tuple):
    """The `mesh_hierarchy()` result: indexes like the legacy 4-tuple
    `(dcn_axis, dp_axis, dcn_size, dp_size)` every existing consumer
    unpacks, plus the tensor-parallel factorization as attributes —
    `model_axis` (None when mp == 1) and `mp_size`. One predicate,
    every layer."""

    __slots__ = ()
    model_axis = None
    mp_size = 1

    def __new__(cls, dcn_axis, dp_axis, dcn_size, dp_size,
                model_axis=None, mp_size=1):
        if model_axis is not None and int(mp_size) > 1:
            cls = _MeshHierarchyTP
        self = tuple.__new__(cls, (dcn_axis, dp_axis, int(dcn_size),
                                   int(dp_size)))
        if cls is _MeshHierarchyTP:
            self._model_axis = model_axis
            self._mp_size = int(mp_size)
        return self

    @property
    def dcn_axis(self):
        return self[0]

    @property
    def dp_axis(self):
        return self[1]

    @property
    def dcn_size(self):
        return self[2]

    @property
    def dp_size(self):
        return self[3]


class _MeshHierarchyTP(MeshHierarchy):
    # no __slots__: variable-length tuple subtypes cannot carry slots,
    # so the TP variant pays one instance dict for its two attributes.

    @property
    def model_axis(self):
        return self._model_axis

    @property
    def mp_size(self):
        return self._mp_size


def mesh_hierarchy(mesh):
    """`MeshHierarchy` of a hybrid mesh — indexes like the legacy
    `(dcn_axis, ici_axis, dcn_size, ici_size)` tuple, with
    `.model_axis`/`.mp_size` carrying the tensor-parallel
    factorization — or None for a flat (single-axis / non-hybrid)
    mesh. The one predicate every layer uses to decide hierarchical vs
    flat lowering: a mesh with a model axis is ALWAYS hierarchical
    (even at dcn == 1 — the data axes still need naming), a 2-D
    (dcn, ici) mesh only when dcn > 1 (byte-for-byte the pre-TP
    contract)."""
    if mesh is None:
        return None
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    if DCN_AXIS not in names or ICI_AXIS not in names:
        return None
    dcn = int(mesh.shape[DCN_AXIS])
    ici = int(mesh.shape[ICI_AXIS])
    if MODEL_AXIS in names and int(mesh.shape[MODEL_AXIS]) > 1:
        return MeshHierarchy(DCN_AXIS, ICI_AXIS, dcn, ici,
                             model_axis=MODEL_AXIS,
                             mp_size=int(mesh.shape[MODEL_AXIS]))
    if dcn <= 1:
        return None
    return MeshHierarchy(DCN_AXIS, ICI_AXIS, dcn, ici)


def mesh_for_world(nranks, dcn=None, dp_axis="dp", devices=None):
    """A device mesh for a hypothetical world of `nranks` of this
    process's devices: the hybrid (dcn, ici) factorization when the
    requested pod count divides it, else a flat 1-D mesh over the
    first `nranks` devices. None when nranks exceeds the local device
    count. Used by Executor.warmup(meshes=[...]) to pre-populate the
    persistent compile cache for other world sizes."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    nranks = int(nranks)
    if nranks < 1 or nranks > len(devices):
        return None
    devs = list(devices[:nranks])
    if dcn is None:
        dcn = dcn_replicas()
    dcn = max(int(dcn), 1)
    mp = model_parallel_degree()
    if mp > 1 and nranks % (dcn * mp) == 0 and nranks > 1:
        return Mesh(
            np.array(devs).reshape(dcn, nranks // (dcn * mp), mp),
            (DCN_AXIS, ICI_AXIS, MODEL_AXIS))
    if dcn > 1 and nranks % dcn == 0:
        return Mesh(np.array(devs).reshape(dcn, nranks // dcn),
                    (DCN_AXIS, ICI_AXIS))
    return Mesh(np.array(devs), (dp_axis,))


def elastic_mesh_variants(mesh=None, min_ranks=1, limit=4,
                          devices=None):
    """The device meshes an elastic shrink would rebuild, most likely
    first: for a base mesh of N devices, the N' = N-1 .. max(min_ranks,
    1) variants (at most `limit`). Pod-aware, mirroring the launch
    supervisor's _pod_shrink policy: a hybrid (dcn, ici) base keeps
    dcn fixed and shrinks ici while N' stays rectangular (divisible by
    dcn), else that N' falls back to the flat single-axis world. A
    tensor-parallel (dcn, ici, model) base keeps BOTH dcn and the
    model degree fixed — a TP group is indivisible — and shrinks the
    replica axis while N' % (dcn * mp) == 0.
    Returns [(n, Mesh)]; `Executor.warmup(meshes="elastic")` (and the
    FLAGS_tpu_warmup_elastic_variants background hook) pre-compiles
    against these so a future shrink's recompile is already in the
    persistent compile cache before the failure happens."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = (list(mesh.devices.flat) if mesh is not None
                   else jax.devices())
    n = len(devices)
    hier = mesh_hierarchy(mesh)
    dp_axis = "dp"
    if mesh is not None and hier is None:
        names = tuple(getattr(mesh, "axis_names", ()) or ())
        if len(names) == 1:
            dp_axis = names[0]
    out = []
    for n2 in range(n - 1, max(int(min_ranks), 1) - 1, -1):
        if len(out) >= int(limit):
            break
        devs = np.array(devices[:n2])
        if (hier is not None and hier.model_axis is not None
                and n2 % (hier[2] * hier.mp_size) == 0 and n2 > 1):
            mp = hier.mp_size
            out.append((n2, Mesh(
                devs.reshape(hier[2], n2 // (hier[2] * mp), mp),
                (hier[0], hier[1], hier.model_axis))))
        elif (hier is not None and hier[2] > 1
                and n2 % hier[2] == 0):
            out.append((n2, Mesh(devs.reshape(hier[2], n2 // hier[2]),
                                 (hier[0], hier[1]))))
        else:
            out.append((n2, Mesh(devs, (dp_axis,))))
    return out


# -- launch env contract (reference: distributed/utils.py:356-360) ----------

def trainer_id() -> int:
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def trainer_num() -> int:
    return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def trainer_endpoints():
    eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
    return eps.split(",") if eps else []


def current_endpoint() -> str:
    return os.environ.get("PADDLE_CURRENT_ENDPOINT", "")
