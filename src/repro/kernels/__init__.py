"""Pallas kernels.  Each kernel module is imported directly; the backend
rule (compiled on an accelerator, interpret mode on the CPU) is
``kernels.lanes.interpret_mode``."""
