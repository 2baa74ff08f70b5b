"""Which device this process runs on — decided here, once.

With ``JAX_PLATFORMS`` unset, a JAX process that cannot get the TPU logs a
warning and carries on on the CPU: a server would then answer from the
CPU, and every Pallas kernel would run in interpret mode, under the same
metric names as a chip run. So the CPU is only ever used when it was asked
for (``JAX_PLATFORMS=cpu``, what the tests and rehearsals set); a process
that was started for the chip and landed anywhere else stops here.
"""

from __future__ import annotations


def requested_platform() -> str:
    """The platform this process — and every worker that inherits its
    environment — was started for: ``tpu`` unless ``JAX_PLATFORMS`` /
    ``jax_platforms`` names others. Reads configuration only; it never
    initialises a backend, so a coordinator that must stay off the chip can
    call it, and may report it as its workers' platform: a server or worker
    that lands anywhere else refuses to start (``backend_platform``)."""
    import jax

    asked = (jax.config.jax_platforms or "tpu").split(",")
    return "tpu" if "tpu" in asked else asked[0]


def chip_requested() -> bool:
    return requested_platform() == "tpu"


def backend_platform() -> str:
    """``jax.default_backend()`` (initialises the backend), refusing the
    silent CPU fallback of a process that was started for the chip."""
    import jax

    platform = jax.default_backend()
    if platform != "tpu" and chip_requested():
        raise RuntimeError(
            f"this process was started for the TPU (JAX_PLATFORMS="
            f"{jax.config.jax_platforms!r}) but JAX fell back to "
            f"{platform!r}; set JAX_PLATFORMS=cpu to run on the CPU on "
            f"purpose (tests, rehearsals)")
    return platform


def pallas_interpret() -> bool:
    """Interpret mode for every Pallas kernel in ``ops/``: on only when the
    CPU was asked for. On the chip kernels always compile through Mosaic."""
    return backend_platform() != "tpu"
