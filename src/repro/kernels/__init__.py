"""Pallas TPU kernels for the paper's compute hot-spots.

FedCD's performance-critical layers are (a) transport quantization of
model payloads (paper §3.4) and (b) the score-weighted aggregation of
client updates (paper eq 1). Each kernel ships as a package:
``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py`` (jitted public
wrapper), ``ref.py`` (pure-jnp oracle used by tests).

Kernels target TPU (VMEM tiling, 128-lane alignment), run compiled
there, and are validated on the CPU in interpret mode.
"""
import jax


def interpret_mode() -> bool:
    """Pallas ``interpret`` flag for the default backend: the CPU
    interprets the kernel body, the TPU compiles it, and any other
    backend is refused rather than silently interpreted."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
