"""The Pallas kernels of the main path, a file a kernel family."""

import jax


def _interpret(flag):
    """A kernel's ``interpret`` argument: None means interpreted off the TPU
    (the CPU tests) and compiled on it."""
    return jax.default_backend() == "cpu" if flag is None else flag
