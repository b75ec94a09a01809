"""Keep helper processes off the accelerator.

A TPU chip belongs to one process at a time: the first process whose jax
initializes the TPU backend holds the chip until it exits, and a second
one that tries fails or hangs.  So exactly one process of a job — the
trainer or the server — may reach the chip, and every framework-spawned
helper that only needs numpy or host-side jax (store server, RPC/PS
workers, DataLoader workers, elastic supervisors) pins the CPU backend
*before* its first backend touch, with ``helper_process_init()``.

A process started by us gets ``JAX_PLATFORMS=cpu`` in its environment
where we control that environment (it works on this installation; the
driver's own test command relies on it).  ``pin_cpu`` is the in-process
form for children whose environment we do not write (multiprocessing
workers inherit the parent's).
"""
from __future__ import annotations


def backend_initialized() -> bool:
    """True iff a PJRT backend has already been created in this process.

    Never triggers backend initialization itself.
    """
    from jax._src import xla_bridge
    return bool(xla_bridge._backends)


def pin_cpu(num_devices: int | None = None) -> bool:
    """Force this process onto the virtual CPU backend if (and only if) no
    backend exists yet.  Returns True when the pin took effect.

    ``num_devices`` provisions that many virtual CPU devices (overrides any
    ``--xla_force_host_platform_device_count`` in XLA_FLAGS).
    """
    if backend_initialized():
        return False
    import jax

    jax.config.update("jax_platforms", "cpu")
    if num_devices:
        jax.config.update("jax_num_cpu_devices", int(num_devices))
    return True


def helper_process_init(num_devices: int | None = None) -> None:
    """Call first thing in every framework-spawned helper process."""
    pin_cpu(num_devices)
