"""Pallas TPU kernels for the compute hot spots, each with a jit'd wrapper
(ops.py) and a pure-jnp oracle (ref.py). Kernels target TPU BlockSpec/VMEM
tiling; the wrappers run them compiled on an accelerator and interpreted
on the CPU backend (`interpret_mode`)."""
import jax


def interpret_mode() -> bool:
    """The one place that decides how a wrapper runs its kernel: in the
    Pallas interpreter on the CPU backend, compiled everywhere else — on
    a TPU a kernel compiles or raises, it never falls back."""
    return jax.default_backend() == "cpu"
