"""TPU-native compute ops: attention (reference + Pallas), sampling, rotary."""

KERNEL_BACKENDS = ("pallas", "pallas_interpret", "reference")


def resolve_kernel_backend(name: str) -> str:
    """One of KERNEL_BACKENDS for a ``VDT_USE_PALLAS`` value.

    ``auto`` means the compiled Pallas kernels and therefore needs a TPU:
    on any other platform it is an error, never a quiet switch to the
    XLA reference — a run that was meant for the chip must not measure
    something else.  CPU runs (tests, rehearsals) ask for ``reference``
    or ``pallas_interpret`` by name."""
    if name == "auto":
        import jax

        platform = jax.default_backend()
        if platform != "tpu":
            raise RuntimeError(
                "VDT_USE_PALLAS=auto selects the compiled Pallas kernels, "
                f"which need a TPU, but JAX found platform {platform!r}; "
                "to run here set VDT_USE_PALLAS=reference (XLA ops) or "
                "VDT_USE_PALLAS=pallas_interpret (interpreted kernels)"
            )
        return "pallas"
    if name not in KERNEL_BACKENDS:
        raise ValueError(
            f"unknown VDT_USE_PALLAS value {name!r}; expected auto or one "
            f"of {KERNEL_BACKENDS}"
        )
    return name
