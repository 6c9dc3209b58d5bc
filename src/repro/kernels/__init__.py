"""Pallas TPU kernels (decode attention, flash prefill, RG-LRU and RWKV-6
scans), each with a jnp oracle in ``ref``.  The model does not call them
yet.  Tests run them in interpret mode against the oracles, and
``tests/test_tpu_compile.py`` compiles them for a v5e chip."""
