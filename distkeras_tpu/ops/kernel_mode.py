"""How the Pallas kernels of this package run: compiled or interpreted.

The one place that decides it. On a TPU every kernel is compiled by
Mosaic (``interpret=False``) and a Mosaic failure is raised to the
caller — nothing catches it and re-routes to an XLA reference. On any
other platform (the CPU mesh the tests run on) the same kernels run in
the Pallas interpreter. The choice is made from the platform at trace
time; ``pallas_interpret()`` is what a script prints to say which ran.
"""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """False on a TPU (Mosaic-compiled kernels), True anywhere else."""
    return jax.default_backend() != "tpu"
