"""One module per configuration, beside its ``<name>.json``.

Each module gives the pieces that the one generator (``lib/build.py``)
and the plain reference need, under the same names:

* ``program_loss(cfg)``           the system's own model loss (under test);
* ``init_params(cfg, key)``       the weights, made by the benchmark from
                                  the seed (the harness jits it);
* ``reference_loss(cfg, p, b, dot)``  the plain reference of the same
                                  loss, in whatever dtype ``p`` holds, its
                                  matrix products through ``dot``;
* ``make_data(cfg, n, kind)``     the client data as host arrays
                                  (``kind="staged"``), or ``None`` for data
                                  the round synthesizes (``"on_demand"``);
* ``program_synth(cfg, n)``       the system's on-demand data object;
* ``reference_block(cfg, ids)``   the plain reference of that synthesis;
* ``round_counts(cfg, n, k)``     (FLOPs, HBM bytes) one round needs at
                                  least, from shapes.
"""
