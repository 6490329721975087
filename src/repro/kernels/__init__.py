"""Pallas kernels of the VP's hot path (crossbar VMM, fused LIF step)."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run under the interpreter: only on the CPU
    backend, where the tests run.  Everywhere else they compile, and a
    kernel that fails to lower raises — it never falls back to the
    interpreter or to the jnp reference.  Read at trace time."""
    return jax.default_backend() == "cpu"
