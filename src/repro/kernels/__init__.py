"""Pallas TPU kernels (validated on CPU via interpret=True).

Import each kernel from its own submodule (``repro.kernels.bitplane_profile``,
``repro.kernels.ops``, ...): the package loads none of them, so the CIM path
does not pull in the attention and state-space kernels.
"""
