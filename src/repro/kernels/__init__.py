"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships a jit'd wrapper (ops.py) and a pure-jnp oracle (ref.py).
The CPU is for tests: there they run in interpret mode
(tests/test_kernels.py).  The mixing kernels are compiled for a described
TPU v5e in tests/test_tpu_compile.py and run natively on the chip by
``python chip_smoke.py``; attention and SSD are selectable in the model
stack via ModelConfig.use_kernels.
"""
from repro.kernels.ops import attention_op, mix_op, ssd_op  # noqa: F401
