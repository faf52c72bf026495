"""Device mesh + sharding placement helpers.

The sync trainer's entire communication story (replacing the reference's
pull/commit socket protocol, reference: distkeras/parameter_servers.py ->
SocketParameterServer) is: params replicated over a 1-D ``Mesh(("data",))``,
batches sharded along "data", loss averaged over the global batch inside
``jit`` — XLA inserts the gradient ``psum`` over ICI automatically.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# THE shard_map accessor for the whole repo: every parallel module routes
# through this alias.
shard_map = jax.shard_map


def local_devices(n=None):
    devs = jax.devices()
    if n is None:
        return devs
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return devs[:n]


def make_mesh(num_devices=None, axis_names=("data",), devices=None) -> Mesh:
    """1-D (default) or n-D mesh over the first ``num_devices`` devices."""
    devs = devices if devices is not None else local_devices(num_devices)
    n = len(devs)
    if len(axis_names) == 1:
        shape = (n,)
    else:
        # factor n into len(axis_names) axes, largest-first
        shape = []
        rem = n
        for _ in axis_names[:-1]:
            f = _largest_factor(rem)
            shape.append(f)
            rem //= f
        shape.append(rem)
        shape = tuple(shape)
    return Mesh(np.array(devs).reshape(shape), axis_names)


def _largest_factor(n):
    for f in range(int(n**0.5), 0, -1):
        if n % f == 0:
            return max(f, n // f)
    return n


def force_cpu_mesh(num_devices: int = 8) -> None:
    """Pin a ``num_devices``-virtual-device CPU platform. Must run before
    the JAX backend initializes: the forced host device count is read from
    XLA_FLAGS at backend init, and ``jax.config.update`` selects the cpu
    platform for this process whatever ``JAX_PLATFORMS`` says. This is the
    one supported way to exercise multi-device code paths without
    accelerator hardware — tests/conftest.py and every example's ``--cpu``
    flag route through the same mechanism."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={int(num_devices)}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")


def serving_mesh(spec, *, devices=None, axis_name: str = "model") -> Mesh:
    """THE serving-mesh constructor: every consumer (``ServingEngine``,
    the decode bench, the soak, the tests) resolves its tensor-parallel
    mesh here instead of re-rolling ``Mesh(jax.devices()[:n], ...)``.

    ``spec`` forms:

    - ``"tp:N"`` — N-way tensor parallelism over the first N devices
      (``devices`` overrides the pool);
    - an int ``N`` — same as ``"tp:N"``;
    - a ``jax.sharding.Mesh`` — passed through after validating it
      carries ``axis_name`` (an engine cannot shard over an axis its
      partition specs never name).

    Validation is LOUD and happens at construction (bundle load), not
    at the first decode step: asking for more ways than there are
    devices raises ``ValueError`` naming both numbers, so a misplaced
    replica fails its boot health-check instead of wedging later.
    """
    if isinstance(spec, Mesh):
        if axis_name not in spec.axis_names:
            raise ValueError(
                f"serving mesh must carry a {axis_name!r} axis; got "
                f"axes {spec.axis_names}"
            )
        return spec
    if isinstance(spec, str):
        kind, sep, num = spec.partition(":")
        if kind != "tp" or not sep or not num.isdigit():
            raise ValueError(
                f"unrecognized serving mesh spec {spec!r}; expected "
                f"'tp:N', an int, or a jax.sharding.Mesh"
            )
        n = int(num)
    else:
        n = int(spec)
    if n < 1:
        raise ValueError(f"serving mesh needs >= 1 device; got tp:{n}")
    devs = devices if devices is not None else jax.devices()
    if n > len(devs):
        raise ValueError(
            f"serving mesh 'tp:{n}' needs {n} devices but only "
            f"{len(devs)} are available — shrink the mesh or run on a "
            f"host with more devices"
        )
    return Mesh(np.array(devs[:n]), (axis_name,))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def shard_batch(batch: dict, mesh: Mesh, axis: str = "data"):
    """Place a host batch dict on the mesh, split along the leading dim."""
    sh = batch_sharding(mesh, axis)
    return {k: jax.device_put(v, sh) for k, v in batch.items()}


def replicate(tree, mesh: Mesh):
    """Replicate a pytree (params/opt state) across the mesh."""
    sh = replicated_sharding(mesh)
    return jax.device_put(tree, sh)


def host_gather(tree):
    """Make every array leaf host-fetchable. In multi-controller runs a
    leaf sharded across processes spans non-addressable devices and
    ``np.asarray`` refuses it; such leaves are all-gathered to a full
    host array first (fully-replicated leaves fetch directly even when
    their device set spans processes)."""

    def fix(x):
        if not isinstance(x, jax.Array):
            return x
        if x.is_fully_addressable or x.sharding.is_fully_replicated:
            return x
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(x, tiled=True)

    return jax.tree.map(fix, tree)


def zero_leaf_sharding(mesh: Mesh, leaf, axis: str = "data") -> NamedSharding:
    """ZeRO-1 placement rule for one optimizer-state leaf: shard the
    FIRST dimension divisible by the ``axis`` size; leaves with no such
    dimension (scalars, small biases) replicate. Params stay replicated —
    sharding only the moments means the update math runs on each rank's
    slice and XLA inserts one all-gather per parameter per step to
    rebuild the replicated p_new (the classic ZeRO-1 collective), cutting
    per-device optimizer memory ~axis-size-fold."""
    n = mesh.shape[axis]
    shape = getattr(leaf, "shape", ())
    for i, d in enumerate(shape):
        if d % n == 0 and d >= n:
            spec = [None] * len(shape)
            spec[i] = axis
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def shard_opt_state_zero(opt_state, mesh: Mesh, axis: str = "data"):
    """Place an optimizer-state pytree with ZeRO-1 shardings
    (``zero_leaf_sharding`` per leaf)."""
    return jax.tree.map(
        lambda x: jax.device_put(x, zero_leaf_sharding(mesh, x, axis)),
        opt_state,
    )
