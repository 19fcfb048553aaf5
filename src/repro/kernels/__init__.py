"""Pallas kernels for the hot spots the paper optimizes (`warp`)."""

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU backend
    only (tests), never on a TPU, where they must lower through Mosaic."""
    return jax.default_backend() == "cpu"
