"""Multi-device test machinery, consolidated.

The main pytest process must keep 1 device (the dry-run is the only
512-device context, per the assignment), so anything that needs a real
multi-device mesh runs under ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` in a subprocess. Three tools, one place:

* ``run_in_subprocess(code, n_devices)`` — run a python snippet in a
  fresh interpreter with N forced host devices (``run_with_devices``
  is the original name, kept as an alias).
* ``host_mesh(n, axis_names)`` — build a named mesh over host devices
  *inside* an already-multi-device process; skips when the process has
  too few devices.
* ``@subprocess_test(n_devices)`` — decorate a test so it re-execs
  ITSELF via ``pytest <nodeid>`` in a subprocess with N forced host
  devices when the current process has too few, and runs in-process
  (no fork) when devices are already available — which is what makes
  the whole suite first-class under the multi-device CI job, where
  XLA_FLAGS is set globally and nothing forks.
"""
import contextlib
import functools
import inspect
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: set in children spawned by subprocess_test — a belt-and-braces guard
#: against recursive re-exec if the forced device count ever fails to
#: materialize (e.g. an XLA that ignores the flag)
_SUBPROC_ENV = "REPRO_SUBPROCESS_TEST"


def run_in_subprocess(code: str, n_devices: int,
                      timeout: int = 600) -> str:
    """Run ``code`` in a fresh interpreter with ``n_devices`` forced
    host devices; assert success and return stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    # forced host devices live on the CPU; a parent holding an
    # accelerator must not have its children contend for it
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


#: original name — existing tests keep working unchanged
run_with_devices = run_in_subprocess


@contextlib.contextmanager
def host_mesh(n, axis_names=("pp",)):
    """Yield a ``jax.sharding.Mesh`` over host devices. ``n`` is an int
    (1-D mesh) or a shape tuple matching ``axis_names`` (e.g.
    ``host_mesh((2, 4), ("pp", "cp"))``). Skips the test when the
    process has fewer devices than the mesh needs — pair with
    ``@subprocess_test`` (or the multi-device CI job's global
    XLA_FLAGS) to guarantee they exist."""
    import jax
    import numpy as np
    import pytest
    from jax.sharding import Mesh
    shape = (n,) if isinstance(n, int) else tuple(n)
    assert len(shape) == len(axis_names), (shape, axis_names)
    total = 1
    for k in shape:
        total *= k
    devs = jax.devices()
    if len(devs) < total:
        pytest.skip(
            f"needs {total} host devices, have {len(devs)} "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{total})")
    with Mesh(np.array(devs[:total]).reshape(shape), axis_names) as m:
        yield m


def subprocess_test(n_devices: int, timeout: int = 1200):
    """Decorator: run the test in-process when ``jax.device_count() >=
    n_devices``, otherwise re-exec exactly this test node via pytest in
    a subprocess with the forced host device count. The test body can
    then use ``host_mesh`` / plain jax APIs as if the devices were
    always there."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(request, *args, **kwargs):
            import jax
            if (jax.device_count() >= n_devices
                    or os.environ.get(_SUBPROC_ENV) == "1"):
                return fn(*args, **kwargs)
            env = dict(os.environ)
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={n_devices}"
            env[_SUBPROC_ENV] = "1"
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = os.path.join(REPO, "src")
            proc = subprocess.run(
                [sys.executable, "-m", "pytest", "-x", "-q",
                 "-p", "no:cacheprovider", request.node.nodeid],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=timeout)
            assert proc.returncode == 0, (
                f"subprocess test {request.node.nodeid} failed "
                f"under {n_devices} devices:\nSTDOUT:\n{proc.stdout}\n"
                f"STDERR:\n{proc.stderr}")

        # pytest resolves fixtures from the SIGNATURE: expose `request`
        # plus the wrapped test's own params (dedup in case it already
        # asks for request). __signature__ wins over __wrapped__.
        sig = inspect.signature(fn)
        params = list(sig.parameters.values())
        if not any(p.name == "request" for p in params):
            params = [inspect.Parameter(
                "request",
                inspect.Parameter.POSITIONAL_OR_KEYWORD)] + params
        wrapper.__signature__ = sig.replace(parameters=params)
        return wrapper
    return deco
