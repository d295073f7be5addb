"""Graph generators of the benchmark, one module per generator name.

Each module exposes ``generate(params, seed)`` returning ``(u, v, t)`` int
arrays sorted by ``t``.  The seed is the configuration's own: a
configuration is one fixed graph, as a published dataset is, and a run's
``--seed`` presents it relabelled (``bench/drivers/common.variant``), so
that every seed runs the same compiled programs.
"""
