"""Window drivers, one module per kind of traffic mix.

A mix file (``bench/mixes/<mix>.json``) names its driver and holds its
parameters.  A driver module exposes:

* ``prepare(engine, graph, mix, seed)``: set-up work, warming every shape
  the window will use; returns the driver's state;
* ``run_window(state, seconds, on_call=None)``: the measured window,
  calling ``on_call(k, before)`` around its k-th timed call (the harness's
  profiler stretch); returns a :class:`~bench.drivers.common.Window`;
* ``end_to_end(window)``: ``{metric name: value}`` of the end-to-end
  metrics it can compute.
"""
