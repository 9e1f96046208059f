"""MeshWorkerNode: an SPMD (pjit) worker as a Launchpad service.

This is the TPU-pod adaptation of the paper's model: the
Launchpad graph is the *control plane*; inside a MeshWorkerNode the *data
plane* is a pjit-compiled step over a device mesh. The node behaves like a
CourierNode (deferred constructor, courier handle), but the resource
group's requirements carry the mesh geometry, which the launcher hands to
the service as a constructed ``jax.sharding.Mesh``::

    with p.group('learner'):
        learner = p.add_node(MeshWorkerNode(Learner, replay, ckpt_dir))
    launcher.launch(p, resources={
        'learner': {'mesh': (4, 2), 'axes': ('data', 'model')}})

The wrapped class receives ``mesh=<Mesh>`` as a keyword argument. On a real
multi-host platform the launcher would also set the jax distributed env
per host; the single-machine launchers build the mesh from local devices,
handing ``jax.devices()`` out in launch order: each mesh worker takes the
next ``prod(mesh)`` devices (``device_offset``), so N one-device workers
on an N-chip host get a chip each. Workers that outnumber the devices
are refused on an accelerator host; on the CPU (tests) they wrap around
and share them.
"""

from __future__ import annotations

from typing import Any, Optional

from repro.core.addressing import Address, parse_endpoint
from repro.core.handles import Handle, collect_handles
from repro.core.nodes.base import Executable, Node, WorkerContext, set_current_context
from repro.core.nodes.python import CourierHandle, _construct


class _MeshExecutable(Executable):
    def __init__(self, name: str, cls, args, kwargs, address: Address,
                 mesh_shape, mesh_axes):
        self.name = name
        self._cls, self._args, self._kwargs = cls, args, kwargs
        self._address = address
        self._mesh_shape = mesh_shape
        self._mesh_axes = mesh_axes
        self.device_offset = 0          # set by the launcher

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self._mesh_shape:
            n *= s
        return n

    def _build_mesh(self):
        import jax
        devices = jax.devices()
        n_need = self.num_devices
        if len(devices) < n_need:
            raise RuntimeError(
                f"mesh {self._mesh_shape} needs {n_need} devices, "
                f"host platform has {len(devices)} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "before jax initializes, or shrink the mesh resource)")
        end = self.device_offset + n_need
        if end > len(devices) and jax.default_backend() != "cpu":
            raise RuntimeError(
                f"mesh worker {self.name!r} would take devices "
                f"{self.device_offset}..{end - 1}, but the host has "
                f"{len(devices)} {jax.default_backend()} devices: mesh "
                "workers would share a chip (launch fewer workers or "
                "smaller meshes)")
        picked = [devices[(self.device_offset + i) % len(devices)]
                  for i in range(n_need)]
        return jax.make_mesh(
            self._mesh_shape, self._mesh_axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(self._mesh_axes),
            devices=picked)

    def run(self, context: WorkerContext) -> None:
        from repro.core import courier
        context.endpoint = self._address.endpoint
        set_current_context(context)
        mesh = self._build_mesh()
        obj = _construct(self._cls, self._args,
                         dict(self._kwargs, mesh=mesh))
        endpoint = self._address.endpoint
        # Dual endpoints (shm://name+grpc://host:port from ProcessLauncher)
        # serve every advertised scheme, same as _CourierExecutable.
        parts = parse_endpoint(endpoint)
        server = None
        try:
            if parts.inproc is not None:
                courier.inprocess.register(parts.inproc, obj)
            if parts.grpc is not None:
                host, port = parts.grpc.rsplit(":", 1)
                server = courier.CourierServer(
                    obj, port=int(port), host=host, shm_name=parts.shm,
                    handler_init=lambda: set_current_context(context))
                server.start()
            elif parts.shm is not None:
                raise ValueError(
                    f"shm endpoint {endpoint!r} needs a grpc:// fallback "
                    "component (launchers always emit dual endpoints)")
            run_fn = getattr(obj, "run", None)
            if callable(run_fn):
                run_fn()
            else:
                context.wait_for_stop()
        finally:
            if parts.inproc is not None:
                courier.inprocess.unregister(parts.inproc)
            if server is not None:
                server.stop()


class MeshWorkerNode(Node):
    """A CourierNode whose service runs SPMD computation over a mesh."""

    DEFAULT_MESH = ((1,), ("data",))

    def __init__(self, cls, *args, **kwargs):
        name = getattr(cls, "__name__", "MeshWorker")
        super().__init__(name=name)
        self._cls, self._args, self._kwargs = cls, args, kwargs
        self.input_handles = collect_handles((args, kwargs))
        self._address = Address(name)

    def addresses(self):
        return (self._address,)

    def create_handle(self) -> Handle:
        h = CourierHandle(self._address)
        self._created_handles.append(h)
        return h

    def to_executables(self, requirements: Optional[dict[str, Any]] = None,
                       launch_type: str = "thread"):
        reqs = requirements or {}
        shape = tuple(reqs.get("mesh", self.DEFAULT_MESH[0]))
        axes = tuple(reqs.get("axes", self.DEFAULT_MESH[1]))
        if len(shape) != len(axes):
            raise ValueError(f"mesh shape {shape} / axes {axes} mismatch")
        return [_MeshExecutable(self.name, self._cls, self._args,
                                self._kwargs, self._address, shape, axes)]
