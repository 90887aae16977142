"""Operation and byte counts of the kernels and steps the benchmark
reads, and the chips' peaks (``peaks.json``).

Each count is of the work the model requires at a call's real shapes:
the prompt's real tokens and the cache's valid positions, never a padded
bucket or block.  So no kernel can read above its roofline by doing less
padding, and the count does not depend on what implements the call.
``dims`` is the dictionary ``reference/dense.py`` derives from a
configuration.
"""
