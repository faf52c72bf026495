"""Backend selection: the chip, or an error.

The reference delegated platform choice to Spark executor config; here the
platform is JAX's. A harness or example either asks for the CPU
(``cpu=True``: the virtual CPU mesh the tests run on) or gets JAX's own
default, which must be a TPU. Nothing turns a missing chip into a CPU
run: a measurement or a demo that was asked for the chip and finds none
fails, naming what it found.

One process owns a chip. Call this once, in the process that will run
the model, before its first compile.
"""

from __future__ import annotations


def setup_backend(cpu: bool = False, cpu_devices: int = 1) -> str:
    """``cpu=True``: pin a ``cpu_devices``-wide virtual CPU mesh and
    return ``"cpu"``. Otherwise initialize JAX's default backend and
    return ``"tpu"``, raising ``RuntimeError`` if it is anything else."""
    from distkeras_tpu.parallel.mesh import force_cpu_mesh

    if cpu:
        force_cpu_mesh(cpu_devices)
        return "cpu"
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError as exc:
        raise RuntimeError(
            f"the chip was asked for and JAX found no backend: {exc}"
        ) from exc
    if dev.platform != "tpu":
        raise RuntimeError(
            f"the chip was asked for and JAX found {len(jax.devices())} x "
            f"{dev.platform} ({dev.device_kind}); pass --cpu to run on the "
            "CPU mesh on purpose"
        )
    return "tpu"
