"""Production mesh builders.

jax is imported INSIDE every function (never at module level) so importing
this module touches neither jax nor device state — required both for the
dry-run's ``xla_force_host_platform_device_count`` trick and for
``set_host_device_count`` below, which must run before jax initializes its
backend (``launch/serve.py --devices N`` calls it before ``import jax``).
"""
from __future__ import annotations

import os
import sys

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def set_host_device_count(n: int) -> None:
    """Expose ``n`` virtual CPU devices (the SNIPPETS ``set_cpu_cores``
    idiom): sets ``XLA_FLAGS=--xla_force_host_platform_device_count=n``.

    Must run before jax initializes its backend — the flag is read once at
    backend init and silently ignored afterwards. If jax is already imported
    we probe the backend: an already-initialized backend with a different
    device count is a hard, *clear* error (the alternative is a mesh build
    failing later with an opaque "requires 4 devices, got 1")."""
    n = int(n)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_HOST_COUNT_FLAG)]
    flags.append(f"{_HOST_COUNT_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    if "jax" in sys.modules:
        import jax

        # device_count() initializes the backend: if it was NOT yet
        # initialized it picks up the flag we just set (count == n, fine);
        # if it WAS initialized the flag came too late — error clearly.
        have = jax.local_device_count()
        if have != n and jax.default_backend() == "cpu":
            raise RuntimeError(
                f"jax already initialized with {have} host device(s); "
                f"set_host_device_count({n}) (or --devices {n}) must run "
                "before the first jax backend use — move it ahead of any "
                "jax import/computation, or set XLA_FLAGS="
                f"{_HOST_COUNT_FLAG}={n} in the environment")


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips (DCN over 'pod')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic helper: any (shape, axes) over the available devices."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    return make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(devices: int = 0):
    """1-D serving mesh: the fleet axis of a ``FleetGroup`` slab maps over
    ``devices`` devices (all visible devices when 0) so F replicas decode on
    N devices in parallel. On a CPU box call ``set_host_device_count(N)``
    (or ``serve.py --devices N``) *before* any jax use; on GPU/TPU the real
    devices are used as-is."""
    import jax

    n = int(devices) or jax.local_device_count()
    return make_mesh((n,), ("fleet",))


def parse_mesh_spec(spec: str):
    """'2x8x16:data,expert,model' -> mesh. Same chip count, refactored axes
    (e.g. a dedicated expert axis for MoE archs whose expert count does not
    divide the data axis)."""
    shape_s, axes_s = spec.split(":")
    shape = tuple(int(x) for x in shape_s.split("x"))
    axes = tuple(axes_s.split(","))
    return make_mesh(shape, axes)
