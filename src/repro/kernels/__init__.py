"""Pallas kernels. Each compiles for the TPU it runs on and runs in the
Pallas interpreter on any other backend; `resolve_interpret` is the one
place that decides which."""
import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """`interpret` as given, or — when None — True unless the program
    runs on a TPU. Kernel entry points default to None, so a TPU run
    always compiles its kernels and a failure to compile is an error."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
