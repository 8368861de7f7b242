"""Plain references of the served/trained architectures: the published layer
equations in straightforward ``jax.numpy`` and float32 — no kernels, no
cache, no batching. The benchmark keeps its own copies under
``benchmark/lib/`` (``tests/test_falcon_h1.py``, ``test_openpangu_moe.py`` and ``test_laguna.py``
pin each pair alike)."""
