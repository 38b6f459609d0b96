"""Production mesh construction (TPU v5e target).

FUNCTIONS, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialisation).

Two mesh families:

* training/decode meshes — ``(data, model)`` (+ a leading ``pod`` axis
  multi-pod): the layouts ``sharding/rules.py`` partitions parameters
  over;
* the SERVING mesh — ``("hosts", "data", "model")``: an explicit host
  PLACEMENT axis ahead of the per-host compute axes.  ``hosts`` is not a
  sharding axis — ``mesh_axes`` excludes it from the data axes — it
  partitions the device set into the per-host submeshes
  (``host_submesh``) that ``serve/topology.py::HostTopology.from_mesh``
  places synthesis waves over.

Every mesh here has ``Auto`` axes: the sharding rules and the serving
path give shardings with ``NamedSharding`` and let XLA propagate them.
``jax.make_mesh`` defaults to ``Explicit`` axes, under which reshapes
such as the DiT's ``unpatchify`` of a row-sharded batch are refused.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

from repro.sharding.rules import MeshAxes


def _auto_mesh(shape: tuple, axes: tuple) -> Mesh:
    _validate_device_count(shape, axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _validate_device_count(shape: tuple, axes: tuple):
    """Fail fast with an actionable error instead of deep inside
    ``jax.make_mesh`` when the runtime has fewer devices than the mesh
    needs (``make_mesh`` itself tolerates a surplus — it takes a
    prefix)."""
    need = int(np.prod(shape))
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} {jax.default_backend()} device(s) are visible — run "
            f"on the pod this mesh targets, or build a local mesh with "
            f"make_host_mesh(data, model) / make_serving_mesh(hosts=..., "
            f"data=..., model=...) sized to jax.device_count()")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_serving_mesh(*, hosts: int = 1, data: int = 1, model: int = 1):
    """Serving mesh: ``hosts`` placement groups, each a (data, model)
    compute submesh.  ``hosts * data * model`` must not exceed the
    visible device count."""
    if min(hosts, data, model) < 1:
        raise ValueError(f"make_serving_mesh: hosts={hosts} data={data} "
                         f"model={model} must all be >= 1")
    return _auto_mesh((hosts, data, model), ("hosts", "data", "model"))


def mesh_axes(mesh) -> MeshAxes:
    """The (data, model) view of any mesh.  ``model`` is tensor-parallel;
    everything else is batch-parallel EXCEPT the serving mesh's ``hosts``
    axis, which is placement (one submesh per host), never sharding."""
    names = mesh.axis_names
    data = tuple(n for n in names if n not in ("model", "hosts"))
    return MeshAxes(data=data, model="model")


def host_submesh(mesh, host: int):
    """Host ``host``'s compute mesh: the ``hosts`` axis sliced away,
    leaving that host's own (data, model) device block, with the serving
    mesh's axis types."""
    if "hosts" not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names} carry no 'hosts' axis — build "
            f"one with make_serving_mesh(hosts=...)")
    n_hosts = int(mesh.shape["hosts"])
    if not 0 <= host < n_hosts:
        raise ValueError(f"host {host} out of range for a {n_hosts}-host "
                         f"serving mesh")
    axis = mesh.axis_names.index("hosts")
    devices = np.take(mesh.devices, host, axis=axis)
    keep = [i for i, n in enumerate(mesh.axis_names) if n != "hosts"]
    return Mesh(devices, tuple(mesh.axis_names[i] for i in keep),
                axis_types=tuple(mesh.axis_types[i] for i in keep))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/benches."""
    return _auto_mesh((data, model), ("data", "model"))
