"""repro — NUMA-aware FFT convolution (Huang et al., 2021) as a multi-pod
JAX/TPU framework. See README.md."""

__version__ = "1.0.0"
