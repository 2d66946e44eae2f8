"""Pallas TPU kernels (decode attention, flash attention, selective scan,
BabelStream) and the one rule for where they run."""
import jax


def auto_interpret(interpret=None) -> bool:
    """Pallas interpret mode for a kernel call: an explicit choice wins;
    otherwise the real Mosaic lowering on TPU and the interpreter on the
    CPU backend (tests and smoke runs).  Any other backend raises, so a
    process that came up without its chip fails instead of quietly running
    the kernels interpreted."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on 'tpu' (or interpreted on "
                       f"'cpu'); the default backend is {backend!r}")
