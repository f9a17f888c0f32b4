# The paper's primary contribution — implement the SYSTEM here
# (scheduler, optimizer, data path, serving loop, etc.) in the
# host framework. Add sibling subpackages for substrates.
import jax


def host_device() -> jax.Device:
    """The device the control plane's decision models run on: the host's
    first CPU device, or the default backend's first device where JAX has
    no CPU backend.

    The forecast, the balancers, the RL actor and its update, and the
    GPSO/GA plan are networks and populations of a few dozen numbers a
    tick. On an accelerator they would queue behind the serving path's
    programs, and each fetch of their results would wait for the decode in
    flight; on the host they run while the accelerator decodes. On a
    CPU-only machine this is the default device, so nothing moves."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]
