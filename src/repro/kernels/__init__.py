"""Pallas TPU kernels for the paper's hot spots: the batched complex GEMM
(``cgemm``) and the fused tile DFTs (``dft_tile``).

On the CPU backend the kernel bodies run in interpret mode (Python
emulation), which is how the tests check them; on a TPU they compile
through Mosaic.
"""
import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """Interpret mode is for the CPU backend only: there the kernels are
    emulated, everywhere else they compile."""
    return jax.default_backend() == "cpu"


def dot_precision(dtype):
    """In-kernel dot precision: float32 operands take full-precision MXU
    passes (Mosaic's ``fp32`` contract precision), matching the engine's
    XLA matmuls (``repro.core.dft.PRECISION``); bf16 operands need one."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
