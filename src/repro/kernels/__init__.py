"""TPU Pallas kernels for the framework's compute hot-spots.

Layout: <name>.py (pl.pallas_call + BlockSpec) / ops.py (jit wrappers) /
ref.py (pure-jnp oracles).  Their math is validated under
interpret=True on the CPU and their TPU lowering by compiling for a
described v5e (``tests/test_chip_compile.py``); the model layer selects
them via ``impl="pallas"`` (TPU) or ``impl="pallas_interpret"`` (tests).
"""
from repro.kernels import ops, ref  # noqa: F401
