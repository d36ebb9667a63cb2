"""Pallas TPU kernels for the compute hot spots the paper optimizes.

Each kernel ships as a subpackage: ``kernel.py`` (pl.pallas_call + BlockSpec
VMEM tiling), ``ops.py`` (jitted public wrapper doing the load-balancing
setup), ``ref.py`` (pure-jnp oracle used by the allclose test sweeps).
Every kernel launches through :func:`repro.core.execute.pallas_call`, which
runs it in the Pallas interpreter where the program is lowered for the CPU
and compiles it natively where it is lowered for a TPU, under the stable
``name=`` each launch passes and inside the ``kernel`` scope
(``docs/tracing.md``).
"""
