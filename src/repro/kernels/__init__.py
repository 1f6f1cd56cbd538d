"""Pallas TPU kernels for the perf-critical compute hot-spots.

Layout per kernel ``<name>``:
- ``<name>.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
- ``ops.py``    — public wrappers with impl dispatch
- ``ref.py``    — pure-jnp oracles (also the non-TPU execution path)

The package re-exports nothing: a re-exported wrapper named like its
kernel module (``gaussian_blur``) would shadow the module, and
``from repro.kernels import gaussian_blur`` would return the function.
"""
