"""Pallas TPU kernels. The package imports nothing, so loading a kernel
module loads neither ``repro.models`` nor ``repro.core``."""
