"""Pallas kernels for the hot spots of confidential serving.

Each kernel compiles to a TPU custom call when JAX's default backend is the
TPU; on any other backend it runs in Pallas interpret mode (the paged
decode kernels run their bit-aligned jnp emulation instead). The choice is
made here, from the platform, not by callers.
"""

import jax


def interpret_default() -> bool:
    """True off the TPU: Pallas kernels then run interpreted."""
    return jax.default_backend() != "tpu"
