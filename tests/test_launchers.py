"""Launch/execution phases: thread + process launchers, restarts, stop."""

import os
import tempfile
import time

import pytest

from repro import core as lp


class Range:
    def __init__(self, lo, hi):
        self._lo, self._hi = lo, hi

    def get(self):
        return list(range(self._lo, self._hi))


class SumConsumer:
    def __init__(self, producers, out_path):
        self._producers = producers
        self._out = out_path

    def run(self):
        total = sum(sum(p.get()) for p in self._producers)
        with open(self._out, "w") as f:
            f.write(str(total))
        lp.stop_program()


def _producer_consumer(out_path):
    p = lp.Program("pc")
    with p.group("producer"):
        h1 = p.add_node(lp.CourierNode(Range, 0, 10))
        h2 = p.add_node(lp.CourierNode(Range, 10, 20))
    with p.group("consumer"):
        p.add_node(lp.CourierNode(SumConsumer, [h1, h2], out_path))
    return p


def _read(path):
    with open(path) as f:
        return int(f.read())


def test_thread_launcher_inproc():
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "out")
        lp.launch_and_wait(_producer_consumer(out), timeout_s=20)
        assert _read(out) == sum(range(20))


def test_thread_launcher_grpc():
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "out")
        lp.launch_and_wait(_producer_consumer(out), timeout_s=30,
                           force_grpc=True)
        assert _read(out) == sum(range(20))


def test_process_launcher():
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "out")
        launcher = lp.ProcessLauncher()
        launcher.launch(_producer_consumer(out))
        assert launcher.wait(timeout=60)
        assert _read(out) == sum(range(20))


class FlakyOnce:
    def __init__(self, marker):
        self._marker = marker

    def run(self):
        if not os.path.exists(self._marker):
            open(self._marker, "w").close()
            raise RuntimeError("first attempt crashes")
        lp.stop_program()


def test_thread_restart_policy_recovers():
    with tempfile.TemporaryDirectory() as d:
        p = lp.Program("flaky")
        p.add_node(lp.PyNode(FlakyOnce, os.path.join(d, "m")))
        launcher = lp.ThreadLauncher(
            restart_policy=lp.RestartPolicy(max_restarts=2, backoff_s=0.01))
        launcher.launch(p)
        assert launcher.wait(timeout=20)
        assert len(launcher.failures) == 1
        assert not launcher.failures[0].fatal


def test_process_restart_policy_recovers():
    with tempfile.TemporaryDirectory() as d:
        p = lp.Program("flaky")
        p.add_node(lp.PyNode(FlakyOnce, os.path.join(d, "m")))
        launcher = lp.ProcessLauncher(
            restart_policy=lp.RestartPolicy(max_restarts=2, backoff_s=0.01))
        launcher.launch(p)
        assert launcher.wait(timeout=60)
        assert len(launcher.failures) == 1 and not launcher.failures[0].fatal


class AlwaysDies:
    def run(self):
        raise RuntimeError("nope")


def test_fatal_after_budget_stops_program():
    p = lp.Program("dead")
    p.add_node(lp.PyNode(AlwaysDies))
    launcher = lp.ThreadLauncher(
        restart_policy=lp.RestartPolicy(max_restarts=1, backoff_s=0.01))
    launcher.launch(p)
    assert launcher.wait(timeout=20)
    assert any(f.fatal for f in launcher.failures)


def test_test_launcher_raises_on_fatal():
    p = lp.Program("dead")
    p.add_node(lp.PyNode(AlwaysDies))
    with pytest.raises(lp.ProgramTestError):
        lp.launch_and_wait(p, timeout_s=20)


class Waits:
    def run(self):
        lp.get_current_context().wait_for_stop(30)


def test_stop_propagates_to_waiting_services():
    p = lp.Program("w")
    p.add_node(lp.PyNode(Waits))
    launcher = lp.ThreadLauncher()
    launcher.launch(p)
    time.sleep(0.1)
    launcher.stop()
    assert launcher.wait(timeout=10)


def test_resources_for_unknown_group_rejected():
    p = lp.Program("t")
    p.add_node(lp.PyNode(Waits))
    with pytest.raises(ValueError, match="unknown groups"):
        lp.ThreadLauncher().launch(p, resources={"nope": {}})


# ---- devices: one per mesh worker, never a forked child on a TPU host ------

_DEVICE_HANDOUT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro import core as lp

got = {}


class Worker:
    def __init__(self, tag, mesh=None):
        got[tag] = [d.id for d in mesh.devices.flat]

    def run(self):
        if len(got) == 4:
            lp.stop_program()


p = lp.Program("devices")
with p.group("solo"):
    for i in range(3):
        p.add_node(lp.MeshWorkerNode(Worker, f"w{i}"))
with p.group("pair"):
    p.add_node(lp.MeshWorkerNode(Worker, "pair"))
lp.launch_and_wait(p, resources={"pair": {"mesh": (2,), "axes": ("data",)}},
                   timeout_s=60)
print(sorted(got.items()))
"""


def test_mesh_workers_get_devices_in_launch_order():
    """The thread launcher hands each mesh worker the next devices: three
    one-device workers get a device each, and a two-device worker takes
    the next two, wrapping around past the last device."""
    import subprocess
    import sys
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
           if k in os.environ}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _DEVICE_HANDOUT],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().splitlines()[-1] == (
        "[('pair', [3, 0]), ('w0', [0]), ('w1', [1]), ('w2', [2])]")


def test_mesh_workers_past_the_last_chip_are_refused(monkeypatch):
    """On an accelerator host a mesh worker that would wrap around onto a
    device another worker holds is refused, not silently doubled up."""
    import jax
    from repro.core.nodes.mesh import _MeshExecutable
    from repro.core.addressing import Address
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = len(jax.devices())
    ex = _MeshExecutable("w", object, (), {}, Address("w"), (1,), ("data",))
    ex.device_offset = n - 1
    assert ex._build_mesh().devices.flat[0] == jax.devices()[n - 1]
    ex.device_offset = n
    with pytest.raises(RuntimeError, match="share a chip"):
        ex._build_mesh()


class _Chipless:
    def __init__(self, mesh=None):
        pass


def test_process_launcher_refuses_mesh_worker_on_tpu_host(monkeypatch):
    """A forked child cannot take a chip its parent holds: on a TPU host
    the process launcher refuses a mesh worker outright, naming the
    launcher that works."""
    from repro.core.launchers import process
    monkeypatch.setattr(process, "_tpu_host", lambda: True)
    p = lp.Program("tpu-mesh")
    with p.group("learner"):
        p.add_node(lp.MeshWorkerNode(_Chipless))
    launcher = lp.ProcessLauncher()
    try:
        with pytest.raises(RuntimeError, match="ThreadLauncher"):
            launcher.launch(p)
    finally:
        launcher.terminate()


def test_tpu_host_detection_respects_platform_pin():
    """With JAX pinned to the CPU (as under test) no chip would be taken,
    so process-launched mesh workers stay allowed."""
    from repro.core.launchers import process
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    assert process._tpu_host() is False
