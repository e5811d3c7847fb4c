"""Model families: one file per architecture, ``bench/families/<name>.py``,
named by a configuration's ``arch["family"]`` and loaded by path from
the run's root (``bench.config.shapes_from``).  There is no default.

A family module provides:

* ``shapes(raw) -> Shapes``: the configuration file read into the sizes
  (a frozen, hashable subclass of ``bench.config.Shapes`` may add the
  family's own fields); it refuses what the program cannot serve.
* ``arch_config(s)``: the program's ``ArchConfig`` for these shapes.
* ``layout(s)``: the shape of every weight leaf, by path, in the
  program's parameter layout; ``scale(path, shape, s)``: each leaf's
  (mean, std).  ``bench/weights.py`` draws them in one jit.
* ``layer(s, lower, x, p)``: one reference layer in float32 at
  ``HIGHEST``, every matrix product through ``bench.reference._mm`` so
  that ``lower`` takes the fp8 control.
* ``matmul_params_per_token(s)``: one layer's matrix parameters that a
  token multiplies, for ``bench/flops.py``.

A family whose work depends on routing would take it from a program
counter instead; none does yet.
"""
