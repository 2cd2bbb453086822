"""Suite-wide setup. MUST run before jax is first imported.

Tests run on the CPU, with Pallas kernels in interpret mode
(``repro.kernels.ops.interpret_mode``). Without an explicit platform, jax
would try the TPU library first on a machine that has it installed; the
suite pins the CPU. The TPU path is covered by compiling for a described
chip (``tests/test_tpu_compile.py``) and run on a chip by
``python chip_smoke.py``. Export JAX_PLATFORMS yourself to override.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
